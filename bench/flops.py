"""Operations from shapes: the counts behind MFU.

Every count here is what the algorithm needs, worked out from the
configuration and the job's shapes, never read from the program:

* ``round_flops``: model FLOPs of one CELU round, the forward and
  backward passes of the 1 + n_local model updates it makes (6 FLOPs per
  matmul weight per row or token, plus attention's 12 * layers * heads *
  head_dim * seq per token).  Recomputation (activation checkpointing)
  and Algorithm 2's extra ad-hoc derivative pass at the label party are
  not counted, and neither are embedding gathers.
"""
from __future__ import annotations

from typing import Dict


def _mlp_weights(dims) -> int:
    return sum(i * o for i, o in zip(dims[:-1], dims[1:]))


def dlrm_wdl_weights(cfg: Dict) -> int:
    """Matmul weights of the split WDL: both towers and the top MLP."""
    hidden, z, E = list(cfg["hidden"]), cfg["z_dim"], cfg["embed_dim"]
    return (_mlp_weights([cfg["fields_a"] * E, *hidden, z])
            + _mlp_weights([cfg["fields_b"] * E, *hidden, z])
            + _mlp_weights([2 * z, hidden[-1], 1]))


def llm_layer_weights(cfg: Dict) -> int:
    d, H, Kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    return d * H * hd + 2 * d * Kv * hd + H * hd * d + 3 * d * f


def llm_weights(cfg: Dict) -> int:
    """Matmul weights of the split LLM: every decoder layer of both
    parties, the fusion projection and the output head."""
    d = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * llm_layer_weights(cfg) + d * d
            + d * cfg["vocab_size"])


def llm_flops_per_token(cfg: Dict, seq_len: int) -> float:
    """FLOPs of one forward + backward pass per token: 6 per matmul
    weight, and attention's scores and values over ``seq_len`` (12 *
    layers * heads * head_dim * seq_len)."""
    attn = 12.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * seq_len
    return 6.0 * llm_weights(cfg) + attn


def round_flops(family, cfg: Dict, job: Dict) -> float:
    """Model FLOPs of one round: 1 + n_local forward + backward passes
    over the batch (n_local = 0 for vanilla training).  ``family`` is
    the configuration's family module, which gives the FLOPs of one
    pass per training row (``train_flops_per_row``)."""
    n_local = job["R"] if job["protocol"] == "celu" else 0
    return (1 + n_local) * job["batch"] \
        * family.train_flops_per_row(cfg, job)
