"""Two-party VFL protocols: Vanilla, FedBCD, CELU-VFL (paper Section 3).

This module is now a thin two-party preset over :mod:`repro.core.engine` —
the single K-party round engine that owns exchange, workset insert/sample,
Algorithm-2 weighting, and the local-update scan.  The public API
(``VFLTask`` / ``init_state`` / ``make_round`` / ``protocol_config`` /
``exchange_bytes``) and the top-level state structure
(``params/opt/ws/steps`` keyed ``"a"``/``"b"`` with scalar step counters)
are unchanged from the original implementation — only the workset
ring-buffer entry keys moved to the engine's generic schema (``"z"`` /
``"dz"`` instead of ``"z_a"`` / ``"dz_a"``; B's slots hold K-lists).
``tests/test_engine.py`` pins the engine's K=1 path against golden traces
recorded from the pre-engine implementation.

A *task* is the minimal two-party interface (information-flow discipline is
kept at function granularity — no function sees both parties' raw data):

    forward_a(params_a, batch_a) -> Z_A
    loss_b(params_b, z_a, batch_b) -> (per_instance_loss (B,), aux_scalar)

One **communication round** exchanges ⟨Z_A, ∇Z_A⟩ once (also performing a
plain SGD step — the "fresh" update) and then runs up to ``R`` *local
updates* per party from its workset table, with round-robin sampling and
staleness-aware instance weighting (Algorithms 1-2):

  * Vanilla  = rounds with R=0 (exchange every step);
  * FedBCD   = consecutive sampling (W=1 semantics) + no weighting;
  * CELU-VFL = round-robin sampling over W slots + cosine weighting.

Communication accounting: each round moves ``bytes(Z_A) + bytes(∇Z_A)``
across the slow link (``engine.SimWANTransport``); the simulated-WAN
wall-clock model used by the benchmarks is ``t_round = bytes / bandwidth +
2 * latency`` (Section 2.1's 213 ms example reproduces with
bandwidth=300 Mbps).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import CELUConfig
from ..optim import Optimizer
from . import engine
from .rows import RowTables


class VFLTask(NamedTuple):
    """Two-party split model interface (see module docstring);
    ``row_tables`` declares the parties' embedding tables
    (``core.rows``); ``counted``: the functions also return counters
    (``engine.KPartyTask``)."""
    forward_a: Callable[[Any, Dict[str, Any]], jnp.ndarray]
    loss_b: Callable[[Any, jnp.ndarray, Dict[str, Any]],
                     Tuple[jnp.ndarray, jnp.ndarray]]
    row_tables: Optional[RowTables] = None
    counted: bool = False


# --------------------------------------------------------------------------
# State (two-party layout <-> engine K=1 layout)
# --------------------------------------------------------------------------
def _to_engine(state):
    return {
        "params": {"a": [state["params"]["a"]], "b": state["params"]["b"]},
        "opt": {"a": [state["opt"]["a"]], "b": state["opt"]["b"]},
        "ws": {"a": [state["ws"]["a"]], "b": state["ws"]["b"]},
        "steps": {"a": [state["steps"]["a"]], "b": state["steps"]["b"]},
        "comm_rounds": state["comm_rounds"],
        "transport": state.get("transport", {}),
    }


def _from_engine(st):
    return {
        "params": {"a": st["params"]["a"][0], "b": st["params"]["b"]},
        "opt": {"a": st["opt"]["a"][0], "b": st["opt"]["b"]},
        "ws": {"a": st["ws"]["a"][0], "b": st["ws"]["b"]},
        "steps": {"a": st["steps"]["a"][0], "b": st["steps"]["b"]},
        "comm_rounds": st["comm_rounds"],
        "transport": st.get("transport", {}),
    }


def init_state(task: VFLTask, params: Dict[str, Any], opt: Optimizer,
               celu: CELUConfig, batch_a: Dict[str, Any],
               batch_b: Dict[str, Any], transport=None, compression=None):
    """Build the full training state.  ``batch_a/b`` are example (abstract ok)
    batches used to size the workset ring buffers;
    ``transport``/``compression`` must mirror :func:`make_round`'s (error
    feedback residuals live in the state)."""
    st = engine.init_state(engine.lift_two_party(task),
                           engine.lift_two_party_params(params),
                           opt, celu, [batch_a], batch_b,
                           transport=transport, compression=compression)
    return _from_engine(st)


def exchange_bytes(z_shape, dtype_bytes: int = 4,
                   wire_dtype: str = "float32") -> int:
    """Bytes moved per communication round (Z_A + ∇Z_A).  The paper sends
    fp32; the beyond-paper bf16 wire halves it."""
    import numpy as np
    if not wire_dtype:
        return 2 * int(np.prod(z_shape)) * dtype_bytes
    tp = engine.SimWANTransport(CELUConfig(wire_dtype=wire_dtype))
    return tp.round_bytes([z_shape])


# --------------------------------------------------------------------------
# One full communication round (exchange + R local updates per party)
# --------------------------------------------------------------------------
def make_round(task: VFLTask, opt: Optimizer, celu: CELUConfig,
               *, local_steps: int = -1, jit: bool = True,
               fused_weighting: bool = True, transport=None,
               compression=None):
    """fn(state, batch_a, batch_b, batch_idx) -> (state, metrics).

    ``local_steps`` defaults to R (steady state: one fresh insert funds R
    uses).  Vanilla training = ``local_steps=0``.  ``compression`` names a
    wire codec (``core.compression.CODEC_SPECS``) when no explicit
    ``transport`` is given."""
    eng = engine.make_round(engine.lift_two_party(task), opt, celu,
                            local_steps=local_steps, transport=transport,
                            compression=compression,
                            fused_weighting=fused_weighting, jit=False)

    def round_fn(state, batch_a, batch_b, batch_idx):
        st, m = eng(_to_engine(state), [batch_a], batch_b, batch_idx)
        return _from_engine(st), m

    return jax.jit(round_fn, donate_argnums=(0,)) if jit else round_fn


# --------------------------------------------------------------------------
# Named protocol presets (the paper's three competitors)
# --------------------------------------------------------------------------
protocol_config = engine.preset_config
