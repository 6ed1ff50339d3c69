"""Row-sparse embedding tables: differentiate and update only the rows a
batch touches.

A task may declare, per party, the parameter leaves that are field-indexed
tables (:class:`Tables`): leaves of shape ``(F, V, ...)`` that the model
reads only as ``leaf[f, batch[ids][:, f]]``, where the batch's ``(B, F)``
integer ids in ``[0, V)`` index nothing else in the model.  A Wide & Deep
party declares ``Tables("x_a", ("tower/embed",))``.

With such a declaration the engine never forms a dense table gradient:
:func:`compact` takes each field's distinct ids, gathers those rows into a
compact table ``(F, B, ...)``, remaps the batch's ids to positions in it,
and the model's unchanged forward runs on the compact table.  Its
gradient is the table gradient at those rows (the gather's transpose sums
duplicate ids, as it does on the full table), which the optimizer's
``update_rows`` step applies in place (``repro.optim``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Tables(NamedTuple):
    """One party's field-indexed tables: ``leaves`` are ``/``-joined key
    paths of param leaves ``(F, V, ...)`` read only as
    ``leaf[f, batch[ids][:, f]]``; ``batch[ids]`` ``(B, F)`` indexes
    nothing else in the model."""
    ids: str
    leaves: Tuple[str, ...]


class RowTables(NamedTuple):
    """A task's declaration: ``a`` holds for every feature party, ``b``
    for the label party; None where a party has no tables."""
    a: Optional[Tables] = None
    b: Optional[Tables] = None


def _get(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def _put(tree, path: str, value):
    """A copy of the nested dict ``tree`` with the leaf at ``path``
    replaced by ``value``."""
    k, _, rest = path.partition("/")
    out = dict(tree)
    out[k] = _put(tree[k], rest, value) if rest else value
    return out


def unique_ids(ids, vocab: int):
    """``(B, F)`` ids in ``[0, vocab)`` -> ``(rows (F, B), pos (B, F),
    n)``: each field's distinct ids ascending, then out-of-range pads
    ``vocab + k`` (so each row of ``rows`` is sorted and unique);
    ``ids[r, f] == rows[f, pos[r, f]]``; ``n`` counts the distinct
    (field, id) pairs."""
    B = ids.shape[0]

    def one(col):
        iota = jax.lax.iota(jnp.int32, B)
        s, order = jax.lax.sort((col.astype(jnp.int32), iota), num_keys=1)
        new = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
        rows = jnp.sort(jnp.where(new, s, vocab + iota))
        rank = jnp.cumsum(new, dtype=jnp.int32) - 1
        pos = jnp.zeros((B,), jnp.int32).at[order].set(
            rank, unique_indices=True)
        return rows, pos, jnp.sum(new, dtype=jnp.int32)

    rows, pos, n = jax.vmap(one, in_axes=1, out_axes=(0, 1, 0))(ids)
    return rows, pos, jnp.sum(n)


def compact(params, batch, tables: Optional[Tables]):
    """-> ``(params', batch', rows, n)``: every leaf in ``tables`` replaced
    by its rows at the batch's distinct ids ``(F, B, ...)``, the ids by
    their positions among them (:func:`unique_ids`), and the ``rows``
    tree that ``update_rows`` takes (the ids at each table leaf, None
    elsewhere).  ``tables=None`` returns the inputs, ``rows=None``."""
    if tables is None:
        return params, batch, None, None
    shapes = {_get(params, p).shape[:2] for p in tables.leaves}
    if len(shapes) != 1:
        raise ValueError(f"tables {tables.leaves} differ in (fields, "
                         f"vocab): {sorted(shapes)}")
    ((F, V),) = shapes
    rows, pos, n = unique_ids(batch[tables.ids], V)
    at = (jnp.arange(F)[:, None], rows)
    tree = jax.tree_util.tree_map(lambda _: None, params)
    for p in tables.leaves:
        params = _put(params, p, _get(params, p).at[at].get(
            mode="fill", fill_value=0))
        tree = _put(tree, p, rows)
    return params, {**batch, tables.ids: pos}, tree, n
