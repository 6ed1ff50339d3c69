"""The one traffic generator: training rows and token sequences from a seed.

A traffic mix is a data file, ``bench/traffic/<name>.json``; its
``data`` group names a generator below and its parameters.  The
generators are copied from the program's ``repro.data.synthetic`` (the
planted-teacher Criteo-layout table with its aligned epoch iterator, and
the planted-bigram token stream) so that no later change to the program
can move this yardstick.  Each yields, forever and reshuffled per epoch,
``(batch_idx, batch_a, batch_b)`` with the rows both parties hold for
that batch, as numpy arrays.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np

Batch = Tuple[int, Dict[str, np.ndarray], Dict[str, np.ndarray]]


def tabular(seed: int, *, fields_a: int, fields_b: int, vocab: int,
            rows: int, batch: int, label_noise: float = 0.05
            ) -> Iterator[Batch]:
    """Criteo-layout rows (``fields_a`` categorical fields at the feature
    party, ``fields_b`` and the label at the label party).  Labels come
    from a planted teacher: per-(field, value) effects, y ~
    Bernoulli(sigmoid(2 * sum / sqrt(F))), flipped with ``label_noise``."""
    rng = np.random.default_rng(seed)
    F = fields_a + fields_b
    teacher = rng.normal(0.0, 1.0, size=(F, vocab)).astype(np.float32)
    x = rng.integers(0, vocab, size=(rows, F), dtype=np.int32)
    logit = teacher[np.arange(F)[None, :], x].sum(axis=1) / np.sqrt(F)
    p = 1.0 / (1.0 + np.exp(-2.0 * logit))
    y = (rng.random(rows) < p).astype(np.float32)
    flip = rng.random(rows) < label_noise
    y = np.where(flip, 1.0 - y, y).astype(np.float32)
    data = {"x_a": np.ascontiguousarray(x[:, :fields_a]),
            "x_b": np.ascontiguousarray(x[:, fields_a:]), "y": y}
    return _epochs(rng, rows, batch,
                   lambda r: ({"x_a": data["x_a"][r]},
                              {"x_b": data["x_b"][r], "y": data["y"][r]}))


def tokens(seed: int, *, vocab: int, aux_vocab: int, sequences: int,
           seq_len: int, batch: int, follow: float = 0.7
           ) -> Iterator[Batch]:
    """Token sequences with a planted bigram table (each next token
    follows the table with probability ``follow``).  The label party
    holds the tokens and next-token labels; the feature party an aligned
    auxiliary stream hashed into ``aux_vocab``."""
    rng = np.random.default_rng(seed)
    trans = rng.integers(0, vocab, size=(vocab,), dtype=np.int32)
    toks = np.empty((sequences, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=(sequences,))
    for t in range(seq_len):
        keep = rng.random((sequences,)) < follow
        toks[:, t + 1] = np.where(keep, trans[toks[:, t]],
                                  rng.integers(0, vocab, size=(sequences,)))
    tok, lab = toks[:, :-1], toks[:, 1:]
    tok_a = ((tok.astype(np.int64) * 2654435761) % aux_vocab
             ).astype(np.int32)
    return _epochs(rng, sequences, batch,
                   lambda r: ({"tokens_a": tok_a[r]},
                              {"tokens": tok[r], "labels": lab[r]}))


def _epochs(rng, n: int, batch: int, take) -> Iterator[Batch]:
    """Both parties draw the same permutation (aligned rows); every full
    batch of an epoch once, then a fresh permutation."""
    if n < batch:
        raise ValueError(f"traffic holds {n} rows, fewer than one batch "
                         f"of {batch}")
    idx = 0
    while True:
        perm = rng.permutation(n)
        for s in range(0, n - batch + 1, batch):
            a, b = take(perm[s:s + batch])
            yield idx, a, b
            idx += 1


GENERATORS = {"tabular": tabular, "tokens": tokens}


def stream(seed: int, spec: Dict) -> Iterator[Batch]:
    """The generator a traffic file's ``data`` group names."""
    spec = dict(spec)
    return GENERATORS[spec.pop("generator")](seed, **spec)
