"""The comparison that decides ``correct`` for a training cell.

Numbers, each a worst or a median case over its parts, compared with
the same number taken from the plain reference that followed the same
first steps from the same weights and rows:

* ``loss_gap``: over the first steps, the largest |loss - reference
  loss| / |reference loss|;
* ``grad_gap``: for every parameter leaf, the norm of the first step's
  gradients as the optimizer received them, read back from the AdaGrad
  accumulator after step 1 (sqrt of its sum: the root of the summed
  squared norms of the step's updates); the gap between the program's
  and the reference's norm, over the larger of the reference leaf's
  norm and the median leaf's, worst leaf;
* ``grad_gap_median``: the same gaps, the median leaf's rather than the
  worst: the worst is one small leaf whose gradient is a mostly
  cancelling sum over the batch (a bias), which rounding moves by its
  nature, where the median is steady from seed to seed;
* ``z_gap``, ``dz_gap``: round 1's exchanged cut activation Z and its
  derivative dZ, as party A's workset ring keeps them (a ring stored as
  it is, float32): |program - reference| / |reference| over all rows and
  values.  Round 1's exchange runs before any update, so these read the
  exchange's own arithmetic, where the later numbers also carry what
  AdaGrad's first, sign-like step does to near-zero gradients;
* ``update_gap``: the same for the norm of each leaf's change from its
  initial value after the last first step.  Leaves whose reference
  gradient norm is under a thousandth of the median leaf's are left out
  (their change is round-off, as a bias under a softmax or a row that
  no batch touched); ``update_kept`` counts the rest.

A leaf is named by its path, ``a/...`` or ``b/...`` for the party.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

IGNORE_BELOW = 1e-3


def leaf_paths(tree) -> List[str]:
    import jax
    out = []
    for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(_key(p) for p in path))
    return out


def _key(p) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(p, attr):
            return str(getattr(p, attr))
    return str(p)


def accum_sums(opt_party) -> List:
    """Per-leaf sums of an AdaGrad accumulator, in the parameter leaves'
    order: a float32 tree ``{"accum": tree}``, or int8 codes in sqrt
    space with per-row scales (``{"accum": (leaf, ...), "t"}``, each leaf
    holding ``q`` and ``scale``: the accumulator is (q * scale)^2)."""
    import jax
    import jax.numpy as jnp
    acc = opt_party["accum"]
    if isinstance(acc, tuple) and acc and hasattr(acc[0], "q"):
        return [jnp.sum((a.q.astype(jnp.float32) * a.scale) ** 2)
                for a in acc]
    return [jnp.sum(a.astype(jnp.float32))
            for a in jax.tree_util.tree_leaves(acc)]


def change_norms(params, params0) -> List:
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum((a.astype(jnp.float32)
                              - b.astype(jnp.float32)) ** 2))
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(params0))]


def gaps(prog: Dict, ref: Dict, names: Sequence[str]) -> Dict:
    """``prog`` and ``ref``: {"losses": [...], "grad": per-leaf norms,
    "change": per-leaf norms}.  -> the compared numbers and where the
    worst leaves are."""
    lp, lr = np.asarray(prog["losses"], np.float64), \
        np.asarray(ref["losses"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"{len(lp)} program losses, {len(lr)} reference")
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    gp, gr = np.asarray(prog["grad"], np.float64), \
        np.asarray(ref["grad"], np.float64)
    g = np.abs(gp - gr) / np.maximum(gr, np.median(gr))
    out["grad_gap"] = float(np.max(g))
    out["grad_gap_median"] = float(np.median(g))
    w = int(np.argmax(g))
    out["grad_worst"] = names[w]
    out["grad_worst_norms"] = _norms(gp[w], gr[w], np.median(gr))
    keep = gr >= IGNORE_BELOW * np.median(gr)
    dp, dr = np.asarray(prog["change"], np.float64)[keep], \
        np.asarray(ref["change"], np.float64)[keep]
    d = np.abs(dp - dr) / np.maximum(dr, np.median(dr))
    out["update_gap"] = float(np.max(d))
    w = int(np.argmax(d))
    out["update_worst"] = [n for n, k in zip(names, keep) if k][w]
    out["update_worst_norms"] = _norms(dp[w], dr[w], np.median(dr))
    out["update_kept"] = f"{int(keep.sum())}/{len(keep)}"
    for k in ("z", "dz"):
        if k in prog and k in ref:
            out[k + "_gap"] = cut_gap(prog[k], ref[k])
    out["ignored"] = [n for n, k in zip(names, keep) if not k]
    return out


def cut_gap(prog, ref) -> float:
    """|prog - ref| / |ref| over a whole cut statistic (rows x values);
    1 where the program holds other rows than the reference."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    if prog.shape != ref.shape:
        return 1.0
    return float(np.linalg.norm(prog - ref) / np.linalg.norm(ref))


def _norms(prog, ref, median) -> Dict:
    """The worst leaf's two norms and the reference's median leaf norm,
    which show how a gap came about (a leaf of 0 reads 1)."""
    return {"program": float(prog), "reference": float(ref),
            "reference_median": float(median)}


COMPARED = ("loss_gap", "grad_gap", "grad_gap_median", "update_gap",
            "z_gap", "dz_gap")


def judge(numbers: Dict, limits: Dict) -> Tuple[Dict, bool]:
    """-> ({name: {"value", "limit"}}, all within their limits).  A
    number with no limit, or not finite, is not correct."""
    lim = limits.get("limits", {})
    skip = limits.get("not_compared", {})
    checks, ok = {}, True
    for name in COMPARED:
        if name not in numbers or name in skip:
            continue
        v, limit = numbers[name], lim.get(name)
        checks[name] = {"value": v, "limit": limit}
        if limit is None or not np.isfinite(v) or v > limit:
            ok = False
    return checks, ok
