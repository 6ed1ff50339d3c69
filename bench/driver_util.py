"""What the drivers share: the seed's key, the weights' layout check, and
the host spans of a traced step."""
from __future__ import annotations

import contextlib
import sys
import time

import check


def seed_key(seed: int):
    """A JAX key from any whole seed (more than 32 bits allowed)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def same_layout(made, shapes):
    """The weights the benchmark made must fit the program's parameter
    tree exactly: structure, shapes and dtypes."""
    import jax
    a = jax.tree_util.tree_structure(made)
    b = jax.tree_util.tree_structure(shapes)
    if a != b:
        raise ValueError(f"weights do not fit the program's parameters:\n"
                         f"{a}\nvs\n{b}")
    for x, y, name in zip(jax.tree_util.tree_leaves(made),
                          jax.tree_util.tree_leaves(shapes),
                          check.leaf_paths(made)):
        if x.shape != y.shape or x.dtype != y.dtype:
            raise ValueError(f"weight {name}: {x.shape} {x.dtype}, program "
                             f"wants {y.shape} {y.dtype}")


@contextlib.contextmanager
def no_span(name):
    yield


def span(name):
    """A host span in the profiler's trace (``jax.profiler``)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Phases:
    """Seconds of each part of a set-up, printed on standard error, so
    that a set-up that varies shows where."""

    def __init__(self):
        self.t, self.parts = time.perf_counter(), {}

    def mark(self, name: str):
        now = time.perf_counter()
        self.parts[name] = now - self.t
        self.t = now

    def say(self):
        print("[bench] set-up parts: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in self.parts.items()),
            file=sys.stderr, flush=True)
