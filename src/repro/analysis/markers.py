"""Jaxpr markers: how the auditor sees sanitizer stages and boundary
crossings inside a trace WITHOUT touching production numerics.

The engine's transports and codecs are ordinary Python objects whose ops
disappear into an undifferentiated soup of ``mul``/``convert_element_type``
eqns once traced.  To audit them statically we bind an identity primitive
(``audit_mark``) around the values of interest — but ONLY inside
:func:`instrumented`, an analyzer-scoped context manager that monkeypatches
the registered implementations:

  * ``privacy.wire_noise``          -> sanitizer mark ``dp``
  * ``SimWANTransport._wire_cast``  -> sanitizer mark ``wire``
  * every codec class ``encode``    -> sanitizer mark ``encode`` on the
                                       payload leaves
  * ``workset._encode_leaf``        -> sanitizer mark ``cache`` (declares
                                       the at-rest storage casts)
  * ``PodTransport.send_up/down``   -> boundary mark on the ppermute output

and by wrapping the engine-side transport object in
:class:`AuditedTransport`, which marks every ``send`` result as a
``boundary`` crossing carrying the sanitizer requirements the config
implies.  Production code paths never import this module; the golden
traces cannot see the marks.

The split matters for mutation coverage: sanitizer marks live INSIDE the
registered implementations, the boundary mark lives in the engine-side
proxy — so a mutated transport that skips the registered pipeline still
gets its output marked as a boundary, now carrying unsanitized raw taint.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import mlir

# Identity primitive: abstract-eval and lowering both pass the operand
# through, so a marked trace computes exactly what the unmarked one does.
mark_p = Primitive("audit_mark")
mark_p.def_impl(lambda x, **_: x)
mark_p.def_abstract_eval(lambda aval, **_: aval)
mlir.register_lowering(mark_p, lambda ctx, x, **_: [x])

# vmap rule: the mark rides the batched operand unchanged, so sanitizer
# and boundary marks survive a leading fleet/job axis — the batched-state
# audit (audit.trace_fleet_case) traces vmapped schedules through the
# same taint pass, with boundary avals carrying the job axis.
from jax.interpreters import batching  # noqa: E402

batching.primitive_batchers[mark_p] = \
    lambda args, dims, **params: (mark_p.bind(args[0], **params), dims[0])

# Sanitizer names whose marks "declare" a narrowing precision cast (the
# kernel-contract cast lint whitelists casts flowing into these).
DECLARED_CAST_STAGES = ("wire", "encode", "cache")


def _arrayish(v: Any) -> bool:
    import numpy as np
    return isinstance(v, (jax.Array, np.ndarray)) or hasattr(v, "aval")


def mark(x, *, role: str, name: str, meta: Tuple = ()):
    """Bind ``audit_mark`` over every array leaf of ``x`` (identity)."""
    return jax.tree_util.tree_map(
        lambda leaf: mark_p.bind(leaf, role=role, name=name, meta=meta)
        if _arrayish(leaf) else leaf, x)


def boundary_requirements(tp, celu, direction: str) -> Tuple[str, ...]:
    """The sanitizer stages a raw value must pass before THIS transport
    may release it in ``direction`` — the taint pass's required pattern.

    Registering a new transport = teaching this function (and
    :func:`instrumented` below, if it adds new sanitizer stages) what its
    sends promise; see docs/ANALYSIS.md."""
    from ..core.engine import CompressedWANTransport
    req = ["wire"]
    if isinstance(tp, CompressedWANTransport) and \
            not getattr(tp.codecs[direction], "exact", False):
        req.append("encode")
    if celu.dp_sigma > 0.0:
        req.append("dp")
    return tuple(req)


def boundary_order(tp, celu, direction: str) -> Tuple[Tuple[str, str], ...]:
    """(before, after) sanitizer-ordering constraints at this boundary.

    With a lossy codec under DP the noise must be applied AFTER the
    encode/decode round-trip (on the decoded wire value, residual already
    taken) — noising first both wastes wire bits on noise and lets error
    feedback cancel the mechanism across rounds."""
    from ..core.engine import CompressedWANTransport
    if (isinstance(tp, CompressedWANTransport) and celu.dp_sigma > 0.0
            and not getattr(tp.codecs[direction], "exact", False)):
        return (("encode", "dp"),)
    return ()


class AuditedTransport:
    """Transparent engine-side proxy: forwards everything to the wrapped
    transport and boundary-marks each send's released value (and new
    residual) with the party index, direction, and requirements."""

    def __init__(self, tp, celu):
        self._tp = tp
        self._celu = celu
        self._counts: Dict[str, int] = {}

    def __getattr__(self, name):
        return getattr(self._tp, name)

    def send(self, rng, x, res=None, direction: str = "up"):
        y, new_res = self._tp.send(rng, x, res, direction)
        party = self._counts.get(direction, 0)
        self._counts[direction] = party + 1
        meta = (("direction", direction), ("party", party),
                ("require", boundary_requirements(self._tp, self._celu,
                                                 direction)),
                ("order", boundary_order(self._tp, self._celu, direction)),
                ("transport", type(self._tp).__name__))
        y = mark(y, role="boundary", name=f"{direction}:{party}", meta=meta)
        return y, new_res


class AuditedPodTransport:
    """Same idea for the SPMD pod path: the boundary is the ppermute
    output.  The pod link is in-datacenter DCN with no codec/DP stage
    registered yet, so the requirement set is empty — the audit's value
    here is the host rule plus the collective whitelist (taint.py checks
    no OTHER collective crosses the pod axis)."""

    def __init__(self, tp):
        self._tp = tp
        self._n = 0

    def __getattr__(self, name):
        return getattr(self._tp, name)

    def send_up(self, z):
        y = self._tp.send_up(z)
        self._n += 1
        return mark(y, role="boundary", name=f"up:{self._n - 1}",
                    meta=(("direction", "up"), ("party", self._n - 1),
                          ("require", ()), ("order", ()),
                          ("transport", type(self._tp).__name__)))

    def send_down(self, dz):
        y = self._tp.send_down(dz)
        self._n += 1
        return mark(y, role="boundary", name=f"down:{self._n - 1}",
                    meta=(("direction", "down"), ("party", self._n - 1),
                          ("require", ()), ("order", ()),
                          ("transport", type(self._tp).__name__)))


@contextlib.contextmanager
def instrumented():
    """Patch the registered sanitizer implementations to mark their
    outputs, for the duration of an analyzer trace.  Reentrant-unsafe by
    design (asserts on double entry); always restores on exit."""
    from ..core import compression as C
    from ..core import engine as E
    from ..core import privacy as P
    from ..core import workset as W

    patched: list[tuple[Any, str, Any]] = []

    def patch(owner, attr, wrapper):
        orig = getattr(owner, attr)
        patched.append((owner, attr, orig))
        setattr(owner, attr, wrapper(orig))
        return orig

    # privacy: the DP-noise stage.  privatize routes through the module
    # global wire_noise, and the transports look privatize up at call
    # time, so this one patch covers both the plain-SimWAN path and the
    # compressed transport's noise-after-decode path.
    patch(P, "wire_noise",
          lambda orig: lambda rng, y, cfg: mark(
              orig(rng, y, cfg), role="sanitizer", name="dp"))

    # wire stage: the dtype round-trip every send path shares.
    patch(E.SimWANTransport, "_wire_cast",
          lambda orig: lambda self, x: mark(
              orig(self, x), role="sanitizer", name="wire"))

    # codec encodes: the payload leaves are what the wire carries.
    for cls in (C.IdentityCodec, C.StochasticQuantCodec, C.TopKCodec,
                C.ChainCodec):
        patch(cls, "encode",
              lambda orig: lambda self, rng, x: mark(
                  orig(self, rng, x), role="sanitizer", name="encode"))

    # workset storage codec: at-rest narrowing casts are declared here.
    patch(W, "_encode_leaf",
          lambda orig: lambda store, x, rng: mark(
              orig(store, x, rng), role="sanitizer", name="cache"))

    try:
        yield
    finally:
        for owner, attr, orig in reversed(patched):
            setattr(owner, attr, orig)
