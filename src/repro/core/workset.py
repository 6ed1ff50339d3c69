"""The workset table: a device-resident ring buffer of cached statistics.

Paper §3.1: the table caches ``⟨i, Z_A^(i), ∇Z_A^(i), j⟩`` entries with two
clocks per entry — the insertion timestamp ``i`` (the communication round
that produced it) and the use count ``j``.  Eviction rules:

  * capacity: during the insertion at time ``i``, entries inserted before
    ``i - W + 1`` are dead (the ring buffer overwrites slot ``i mod W``, and
    the validity predicate ``insert_time > time - W`` retires the rest);
  * exhaustion: entries that reach ``R`` uses are dead.

Everything is a fixed-shape pytree of jnp arrays, so insert / sample /
tick are all jittable (``lax.dynamic_*`` only — no Python in the step) and
the table shards like any other training-state leaf (batch dim over the
``data`` mesh axis).

Each party owns its own table.  Besides the exchanged statistics, a party
caches its OWN features for the batch (Party A: ``X_A``; Party B: ``X_B, y``)
so local updates never touch the host — callers pass those through the
generic ``aux`` pytree.

Round-robin sampling (paper §3.2): a cursor walks slots in insertion order;
a slot cannot be re-sampled within ``W-1`` local steps by construction.
Consecutive sampling (FedBCD / the ``W=1`` degenerate case) always returns
the most recently inserted slot.

Storage codec (quantized-at-rest cache)
---------------------------------------
At realistic capacities the table dominates training-state memory, and the
wire statistics it caches tolerate aggressive quantization (Compressed-VFL
— the same result the compressed transport exploits on the wire).
``workset_init(..., cache_dtype=...)`` selects the at-rest precision of
the cut-statistic subtrees (the ``z``/``dz`` entry keys, ``QUANT_KEYS``):

  * ``"float32"`` — store leaves as-is (bit-identical to the historical
    table; the golden traces pin this);
  * ``"bfloat16"`` — leaves stored as bf16 (:class:`CastLeaf`), halving
    the footprint; decode upcasts back to the original dtype;
  * ``"int8"`` — leaves stored as int8 codes with one fp32 absmax scale
    per *instance row* (:class:`QuantLeaf`), quantized on insert with the
    fused Pallas stochastic-rounding kernel (``ops.quantize_stochastic``,
    unbiased: ``E[q * s] == x``).  ~4x smaller.  The row is the tile
    because Algorithm-2's cosine is a row reduction — row-granular scales
    let the fused sample kernel gather + dequantize + weight in one VMEM
    pass without re-tiling.
  * ``"int4"`` — int4 codes (levels = ±7), nibble-packed two per byte
    (:class:`Quant4Leaf`; lane-grouped so the fused kernels unpack with
    aligned slices, see :func:`pack_nibbles`).
    Same per-row fp32 scale, same SR quantizer at ``levels=7``; odd row
    widths pad one zero code before packing (the pad nibble decodes to an
    exact zero, so it contributes nothing to the cosine reductions).
    ~7x smaller than fp32 — the LLM-geometry setting, where the cache is
    a party's dominant training-state allocation.

Cache memory math (per party, ``z`` + ``dz``, scales included):

    cache_bytes(fp32) = 2 * W * B * F * 4
    cache_bytes(int8) = 2 * W * B * (F + 4)        # codes + fp32 row scale
    cache_bytes(int4) = 2 * W * B * (ceil(F/2) + 4)  # packed nibbles

    geometry                          fp32        int8     int4
    paper  W=5 B=4096 F=256         41.9 MB     10.6 MB    5.4 MB
    llm    W=5 B=256  S=64 d=128    83.9 MB     21.2 MB   10.6 MB
    smollm W=5 B=8 S=1024 d=960    1573.0 MB   399.5 MB  196.9 MB

``insert`` and ``sample`` auto-detect the table's storage form — only
``workset_init`` takes ``cache_dtype``.  ``workset_sample`` returns
decoded (full-precision) entries; the fused sample path in
``repro.core.engine`` skips that materialization entirely by handing the
ring + slot to the gather→dequant→weight megakernel
(``kernels/fused_sample.py``).
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

INT_MIN = -(2 ** 30)

# Entry keys holding the exchanged cut statistics — the subtrees the
# storage codec quantizes.  Everything else (own features, labels) is
# cached verbatim.
QUANT_KEYS = ("z", "dz")

CACHE_DTYPES = ("float32", "bfloat16", "int8", "int4")


# --------------------------------------------------------------------------
# Storage containers (registered pytree nodes: traced codes/scales as
# children, static shape/dtype as aux data — jit/scan/shard-safe)
# --------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
class QuantLeaf:
    """int8-at-rest storage of one cached statistic leaf.

    ``q`` holds signed int8 codes of the leaf flattened to (B, F) rows
    (table level: (W, B, F)), ``scale`` one fp32 absmax scale per row as
    a column ((B, 1) / (W, B, 1): the layout the fused kernels read).
    ``shape``/``dtype`` remember the original per-entry leaf so
    :meth:`dequant` can restore it."""

    __slots__ = ("q", "scale", "shape", "dtype")

    def __init__(self, q, scale, shape, dtype):
        self.q = q
        self.scale = scale
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, str(self.dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def dequant(self):
        """Entry-level (q (B, F), scale (B, 1)) -> the original leaf."""
        x = self.q.astype(jnp.float32) * self.scale
        return x.reshape(self.shape).astype(self.dtype)


NIBBLE_GROUP = 128       # packed bytes per lane group (256 codes)


def _groups(n: int):
    """Split a row of ``n`` packed bytes into (full lane groups, rest)."""
    return n // NIBBLE_GROUP, n % NIBBLE_GROUP


def pack_nibbles(q):
    """Signed int4 codes (..., Fp) in [-7, 7] (Fp even) -> packed uint8
    (..., Fp // 2), each code biased by +8 so the zero code is the nibble
    value 8.

    Lane-grouped layout: every 256 consecutive codes pack into 128
    bytes, byte j holding code j of the group in its low nibble and code
    j + 128 in its high nibble; a shorter last group of 2r codes splits
    the same way at r.  A 128-lane block of packed bytes then unpacks on
    the TPU into two contiguous 128-lane blocks of codes
    (``kernels.fused_sample.unpack4``) — no lane interleave."""
    b = (q + 8).astype(jnp.uint8)                  # [-7, 7] -> [1, 15]
    lead = b.shape[:-1]
    n_full, rest = _groups(b.shape[-1] // 2)
    parts = []
    if n_full:
        g = b[..., :n_full * 2 * NIBBLE_GROUP].reshape(
            lead + (n_full, 2, NIBBLE_GROUP))
        parts.append((g[..., 0, :] | (g[..., 1, :] << 4)).reshape(
            lead + (n_full * NIBBLE_GROUP,)))
    if rest:
        t = b[..., n_full * 2 * NIBBLE_GROUP:]
        parts.append(t[..., :rest] | (t[..., rest:] << 4))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def unpack_nibbles(packed):
    """Packed uint8 (..., P) -> signed int4 codes (..., 2 * P) in
    [-8, 7] fp32-safe int8 (the inverse of :func:`pack_nibbles`)."""
    lo = (packed & 0xF).astype(jnp.int8) - 8
    hi = (packed >> 4).astype(jnp.int8) - 8
    lead = packed.shape[:-1]
    n_full, rest = _groups(packed.shape[-1])
    parts = []
    if n_full:
        cut = n_full * NIBBLE_GROUP
        g = jnp.stack([lo[..., :cut].reshape(lead + (n_full, NIBBLE_GROUP)),
                       hi[..., :cut].reshape(lead + (n_full, NIBBLE_GROUP))],
                      axis=-2)
        parts.append(g.reshape(lead + (2 * cut,)))
    if rest:
        parts += [lo[..., -rest:], hi[..., -rest:]]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)


def _pad_even(F: int) -> int:
    return F + (F & 1)


@jax.tree_util.register_pytree_node_class
class Quant4Leaf:
    """int4 nibble-packed at-rest storage of one cached statistic leaf.

    ``q`` holds packed uint8 bytes — two signed int4 codes (levels ±7)
    per byte — of the leaf flattened to (B, F) rows and F padded to even
    (entry level (B, ceil(F/2)); table level (W, B, ceil(F/2))).
    ``scale`` is one fp32 absmax scale per row ((B, 1) / (W, B, 1)),
    exactly like :class:`QuantLeaf`.  The pad nibble stores code 0 so it
    decodes to an exact zero; :meth:`dequant` slices it away."""

    __slots__ = ("q", "scale", "shape", "dtype")

    def __init__(self, q, scale, shape, dtype):
        self.q = q
        self.scale = scale
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, str(self.dtype))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def dequant(self):
        """Entry-level (q (B, ceil(F/2)), scale (B, 1)) -> the original
        leaf."""
        F = 1
        for s in self.shape[1:]:
            F *= int(s)
        codes = unpack_nibbles(self.q)[:, :max(F, 1)]
        x = codes.astype(jnp.float32) * self.scale
        return x.reshape(self.shape).astype(self.dtype)


@jax.tree_util.register_pytree_node_class
class CastLeaf:
    """bf16-at-rest storage of one cached statistic leaf (a plain dtype
    cast; ``dtype`` remembers the original for decode)."""

    __slots__ = ("v", "dtype")

    def __init__(self, v, dtype):
        self.v = v
        self.dtype = jnp.dtype(dtype)

    def tree_flatten(self):
        return (self.v,), (str(self.dtype),)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])

    def decode(self):
        return self.v.astype(self.dtype)


def _is_store(x) -> bool:
    return isinstance(x, (QuantLeaf, Quant4Leaf, CastLeaf))


def _row_shape(a) -> Tuple[int, int]:
    """Leaf (B, ...) -> (rows B, flattened row length F)."""
    B = int(a.shape[0])
    F = 1
    for s in a.shape[1:]:
        F *= int(s)
    return B, max(F, 1)


def _quantize_rows(rng, x2d, levels: int = 127):
    """(B, F) fp32 -> (codes int8 (B, F), fp32 row scales (B, 1)); the
    fused Pallas SR quantizer when the grid can tile B, its bit-identical jnp
    oracle otherwise.  ``levels`` is the max code magnitude (127 = int8 at
    rest, 7 = int4 at rest — the codes come back int8 either way; the int4
    caller nibble-packs them)."""
    from ..kernels.quantize import BLOCK_T
    B = x2d.shape[0]
    u = jax.random.uniform(rng, x2d.shape, jnp.float32)
    if B % min(BLOCK_T, B) == 0:
        from ..kernels import ops as kops
        return kops.quantize_stochastic(x2d, u, levels)
    from ..kernels.ref import quantize_sr_ref
    return quantize_sr_ref(x2d, u, levels)


def _empty_store(W: int, a, cache_dtype: str):
    """Table-level storage for one quantizable leaf."""
    if cache_dtype == "float32":
        return jnp.zeros((W,) + a.shape, a.dtype)
    if cache_dtype == "bfloat16":
        return CastLeaf(jnp.zeros((W,) + a.shape, jnp.bfloat16), a.dtype)
    B, F = _row_shape(a)
    if cache_dtype == "int4":
        # zero scales make the empty table decode to exact zeros, so the
        # packed byte value is immaterial; 0x88 (code 0 in both nibbles)
        # keeps unpack(empty) == 0 too, matching the int8 empty table.
        return Quant4Leaf(jnp.full((W, B, _pad_even(F) // 2), 0x88,
                                   jnp.uint8),
                          jnp.zeros((W, B, 1), jnp.float32), a.shape,
                          a.dtype)
    return QuantLeaf(jnp.zeros((W, B, F), jnp.int8),
                     jnp.zeros((W, B, 1), jnp.float32), a.shape, a.dtype)


def _encode_leaf(store, x, rng):
    """One entry leaf -> the storage form matching the table's leaf (the
    table's shape/dtype metadata wins, like the historical ``astype`` on
    insert coerced the entry to the buffer dtype)."""
    if isinstance(store, Quant4Leaf):
        B, F = _row_shape(x)
        q, scale = _quantize_rows(rng, x.reshape(B, F).astype(jnp.float32),
                                  levels=7)
        if F & 1:                       # pad one zero code before packing
            q = jnp.pad(q, ((0, 0), (0, 1)))
        return Quant4Leaf(pack_nibbles(q), scale, store.shape, store.dtype)
    if isinstance(store, QuantLeaf):
        B, F = _row_shape(x)
        q, scale = _quantize_rows(rng, x.reshape(B, F).astype(jnp.float32))
        return QuantLeaf(q, scale, store.shape, store.dtype)
    if isinstance(store, CastLeaf):
        return CastLeaf(x.astype(jnp.bfloat16), store.dtype)
    return x


def _decode_leaf(leaf):
    if isinstance(leaf, (QuantLeaf, Quant4Leaf)):
        return leaf.dequant()
    if isinstance(leaf, CastLeaf):
        return leaf.decode()
    return leaf


def decode_entry(entry):
    """Storage-form entry -> full-precision entry (identity for fp32)."""
    return jax.tree_util.tree_map(_decode_leaf, entry, is_leaf=_is_store)


def workset_nbytes(ws: Dict[str, Any], keys=None) -> int:
    """Actual device bytes held by the table's ring buffer (codes, scales
    and raw leaves; excludes the O(W) clock vectors).  ``keys`` restricts
    the count to those entry keys — e.g. ``QUANT_KEYS`` for the cut
    statistics the storage codec compresses (the party's raw-feature cache
    is stored verbatim regardless)."""
    buf = ws["buf"] if keys is None else \
        {k: v for k, v in ws["buf"].items() if k in keys}
    return sum(int(leaf.nbytes)
               for leaf in jax.tree_util.tree_leaves(buf))


def sample_hbm_bytes(entry_example: Dict[str, Any],
                     cache_dtype: str = "float32",
                     fused: bool = True, party: str = "a") -> int:
    """Roofline counter: HBM bytes moved by ONE local-update sample over
    the cut statistics — gather from the ring, dequantize, row-cosine
    against the ad-hoc statistics, cotangent scale.  Excludes the
    forward/backward over the party model (identical across paths).

    ``party="a"`` (a feature party) — unfused: the sampled ``z``/``dz``
    rows are gathered into a full-precision entry copy (read stored +
    write fp32), then the weighting kernel re-reads ad-hoc + both copies
    and writes w + cot.  Fused: one pass — read stored z/dz + ad-hoc,
    write w + cot.

    ``party="b"`` (the label party, ``engine.local_grad_b_cached``) — the
    loss CONSUMES the dequantized Z list, so the decoded fp32 z copy is
    always materialized (read stored + write fp32) regardless of fusion;
    only the dz-side cosine weighting fuses against the stored ring (read
    stored dz + ad-hoc, write w + the kernel's ride-along cot)."""
    if cache_dtype not in CACHE_DTYPES:
        raise ValueError(f"cache_dtype must be one of {CACHE_DTYPES}, "
                         f"got {cache_dtype!r}")
    if party not in ("a", "b"):
        raise ValueError(f"party must be 'a' or 'b', got {party!r}")
    z_leaves = jax.tree_util.tree_leaves(entry_example.get("z", {}))
    dz_leaves = jax.tree_util.tree_leaves(entry_example.get("dz", {}))

    def _at_rest(B: int, F: int) -> int:
        if cache_dtype == "int4":            # packed nibbles + row scale
            return B * (_pad_even(F) // 2) + B * 4
        itemsize = {"float32": 4, "bfloat16": 2, "int8": 1}[cache_dtype]
        return B * F * itemsize + (B * 4 if cache_dtype == "int8" else 0)

    total = 0
    for a in z_leaves + dz_leaves:           # the ring reads, at rest
        B, F = _row_shape(a)
        total += _at_rest(B, F)
    if party == "a":
        for a in z_leaves:                   # per ⟨z, dz⟩ pair:
            B, F = _row_shape(a)
            f32 = B * F * 4
            if fused:
                # one pass: + read ad-hoc, write cot + w
                total += f32 + f32 + B * 4
            else:
                # gather writes a fp32 entry copy (z + dz), the weighting
                # kernel re-reads it plus the ad-hoc stats, writes cot + w
                total += 2 * f32 + (3 * f32) + f32 + B * 4
        return total
    for a in z_leaves:                       # decoded Z the loss consumes
        B, F = _row_shape(a)
        total += B * F * 4                   # fp32 copy write, both paths
    for a in dz_leaves:                      # dz-side cosine weighting
        B, F = _row_shape(a)
        f32 = B * F * 4
        if fused:
            # one pass over the stored ring: + read ad-hoc dz,
            # write w + the ride-along cot
            total += f32 + f32 + B * 4
        else:
            # gather writes a decoded fp32 dz copy, the weighting kernel
            # re-reads it plus the ad-hoc dz, writes w
            total += f32 + 2 * f32 + B * 4
    return total


# --------------------------------------------------------------------------
# Table ops
# --------------------------------------------------------------------------
def workset_init(W: int, entry_example: Dict[str, Any], *,
                 cache_dtype: str = "float32") -> Dict[str, Any]:
    """Create an empty table.  ``entry_example`` is a pytree of arrays with
    the per-batch shapes (e.g. {"z": (B,S,d), "dz": (B,S,d), "batch": ...});
    the table stacks a leading W axis.  ``cache_dtype`` selects the at-rest
    storage of the ``z``/``dz`` subtrees (see module docstring); everything
    else is cached verbatim."""
    if cache_dtype not in CACHE_DTYPES:
        raise ValueError(f"cache_dtype must be one of {CACHE_DTYPES}, "
                         f"got {cache_dtype!r}")
    buf = {}
    for k, sub in entry_example.items():
        if k in QUANT_KEYS and cache_dtype != "float32":
            buf[k] = jax.tree_util.tree_map(
                lambda a: _empty_store(W, a, cache_dtype), sub)
        else:
            buf[k] = jax.tree_util.tree_map(
                lambda a: jnp.zeros((W,) + a.shape, a.dtype), sub)
    return {
        "buf": buf,
        "insert_time": jnp.full((W,), INT_MIN, jnp.int32),
        "use_count": jnp.zeros((W,), jnp.int32),
        "batch_idx": jnp.full((W,), -1, jnp.int32),
        "cursor": jnp.int32(0),
        "time": jnp.int32(0),      # communication rounds so far
    }


def workset_insert(ws: Dict[str, Any], entry: Dict[str, Any],
                   batch_idx, *, rng=None) -> Dict[str, Any]:
    """Insert a fresh entry at ring slot ``time mod W``; bump the clock.

    The entry is encoded into the table's storage form first (int8
    stochastic rounding / bf16 cast / verbatim — auto-detected from the
    ring).  ``rng`` seeds the rounding noise for quantized tables; when
    omitted a key is derived from the table clock (deterministic)."""
    W = ws["insert_time"].shape[0]
    t = ws["time"]
    slot = jnp.mod(t, W)

    stores, treedef = jax.tree_util.tree_flatten(ws["buf"],
                                                 is_leaf=_is_store)
    values = treedef.flatten_up_to(entry)
    if rng is None and any(isinstance(s, (QuantLeaf, Quant4Leaf))
                           for s in stores):
        rng = jax.random.fold_in(jax.random.PRNGKey(0xCE1), t)
    encoded = treedef.unflatten([
        _encode_leaf(s, v, None if rng is None
                     else jax.random.fold_in(rng, i))
        for i, (s, v) in enumerate(zip(stores, values))])

    buf = jax.tree_util.tree_map(
        lambda b, e: jax.lax.dynamic_update_index_in_dim(b, e.astype(b.dtype),
                                                         slot, 0),
        ws["buf"], encoded)
    return {
        "buf": buf,
        "insert_time": ws["insert_time"].at[slot].set(t),
        "use_count": ws["use_count"].at[slot].set(0),
        "batch_idx": ws["batch_idx"].at[slot].set(jnp.int32(batch_idx)),
        "cursor": ws["cursor"],
        "time": t + 1,
    }


def _valid_mask(ws: Dict[str, Any], R: int,
                pipeline_staleness=0) -> jnp.ndarray:
    """(W,) bool — alive entries: inserted, not expired, not exhausted.

    ``pipeline_staleness`` tightens the expiry window: under a depth-D
    pipelined schedule every cached entry is D exchanges older by the time
    its sampled round completes, so the oldest D ring slots are retired
    early to keep the paper's max-staleness bound W.  It may be a static
    Python int (depths 0/1) or a traced jnp int scalar — the depth-D
    queue's PER-SLOT offset, which shrinks during warmup/drain when fewer
    exchanges are in flight.  At s >= W no draw is ever valid, which is
    why the scheduler rejects depths >= W up front."""
    t = ws["time"]
    W = ws["insert_time"].shape[0]
    # not expired (the ring overwrite also enforces this at staleness 0)
    alive = ws["insert_time"] >= t - W + pipeline_staleness
    alive &= ws["insert_time"] > INT_MIN    # ever inserted
    alive &= ws["use_count"] < R            # not exhausted
    return alive


def workset_draw(ws: Dict[str, Any], R: int, strategy: str, *,
                 rng=None, pipeline_staleness=0
                 ) -> Tuple[Dict[str, Any], jnp.ndarray, jnp.ndarray,
                            jnp.ndarray]:
    """Pick one slot for a local update WITHOUT materializing the entry.

    strategy: "round_robin" — advance the cursor to the next alive slot
    (uniform over the table); "consecutive" — always the freshest slot
    (FedBCD); "uniform" — an independent uniform draw over the alive slots
    (requires ``rng``; the paper's §3.2 fair-sampling property holds per
    draw instead of per W-cycle).  Returns (new_ws, slot, batch_idx,
    valid) where ``valid`` is a bool scalar (False -> caller must no-op
    the update).  The fused sample path hands ``slot`` straight to the
    gather→dequant→weight megakernel; :func:`workset_sample` keeps the
    materializing form."""
    W = ws["insert_time"].shape[0]
    alive = _valid_mask(ws, R, pipeline_staleness)
    if strategy == "consecutive":
        slot = jnp.mod(ws["time"] - 1, W)
        valid = alive[slot]
        new_cursor = ws["cursor"]
    elif strategy == "uniform":
        if rng is None:
            raise ValueError("uniform sampling needs an rng key")
        # uniform over alive slots; with none alive the draw is degenerate
        # and ``valid`` masks it into a no-op
        logits = jnp.where(alive, 0.0, -jnp.inf)
        logits = jnp.where(jnp.any(alive), logits, jnp.zeros((W,)))
        slot = jax.random.categorical(rng, logits)
        valid = alive[slot]
        new_cursor = ws["cursor"]
    elif strategy == "round_robin":
        # STRICT cycle (paper §3.2 / Fig 4): the cursor advances by exactly
        # one per draw, so a slot cannot be re-sampled within W-1 draws.
        # Dead/empty slots yield an invalid (no-op) draw — the "bubbles" the
        # paper accepts in the first W-1 rounds.  Skipping dead slots
        # instead would collapse the schedule back to consecutive reuse of
        # the freshest batch (measured: identical curves for all W).
        slot = jnp.mod(ws["cursor"], W)
        valid = alive[slot]
        new_cursor = jnp.mod(slot + 1, W)
    else:
        raise ValueError(strategy)

    new_ws = dict(ws)
    new_ws["use_count"] = ws["use_count"].at[slot].add(
        jnp.where(valid, 1, 0))
    if strategy == "round_robin":
        new_ws["cursor"] = new_cursor          # advance even on a bubble
    else:
        new_ws["cursor"] = jnp.where(valid, new_cursor, ws["cursor"])
    return new_ws, slot, ws["batch_idx"][slot], valid


def workset_entry(ws: Dict[str, Any], slot) -> Dict[str, Any]:
    """Materialize (gather + decode) the entry at ``slot``."""
    raw = jax.tree_util.tree_map(lambda b: b[slot], ws["buf"])
    return decode_entry(raw)


def workset_sample(ws: Dict[str, Any], R: int, strategy: str, *,
                   rng=None, pipeline_staleness=0
                   ) -> Tuple[Dict[str, Any], Dict[str, Any], jnp.ndarray,
                              jnp.ndarray]:
    """Draw one entry for a local update: :func:`workset_draw` plus the
    materialized (decoded) entry.  Returns (new_ws, entry, batch_idx,
    valid)."""
    new_ws, slot, batch_idx, valid = workset_draw(
        ws, R, strategy, rng=rng, pipeline_staleness=pipeline_staleness)
    return new_ws, workset_entry(ws, slot), batch_idx, valid


def workset_stats(ws: Dict[str, Any], R: int,
                  pipeline_staleness=0) -> Dict[str, jnp.ndarray]:
    """Table health counters.  ``pipeline_staleness`` must match the
    schedule the table serves: a depth-D pipeline retires the oldest D
    slots early (see :func:`_valid_mask`), so reporting at staleness 0
    would overcount ``n_alive`` under pipelining."""
    alive = _valid_mask(ws, R, pipeline_staleness)
    return {
        "n_alive": jnp.sum(alive),
        "total_uses": jnp.sum(jnp.where(alive, ws["use_count"], 0)),
        "time": ws["time"],
    }
