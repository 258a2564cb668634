"""Embedding lookup whose table gradient is a product where that is cheaper.

``embed_lookup(table, ids, dtype)`` is ``table[ids].astype(dtype)``. The
gradient of a gather is a scatter-add, which a TPU runs one update after
another: on one TPU v5e 17.2M lookups of a 16-wide row into 5,976 rows took
116 ms, and building the float32 ``[lookups, 16]`` cotangent it is fed from
another 58 ms of layout copies (PERF.md, Findings "PR 27"). For a small,
narrow table the same sum is a contraction over the lookups. Split the id,
``v = hi * LO + lo``; then::

    dE[hi, d, lo] = sum_n  L[n, hi, d] * OH[n, lo]
    L[n, hi, d]   = g[n, d] where hi_n == hi else 0
    OH[n, lo]     = 1 where lo_n == lo else 0

a product ``[H * width, n] x [n, LO]`` that fills both sides of the MXU and
costs ``2 * rows * width`` FLOPs a lookup whatever the ids are. One-hots
are exact in every float dtype, so the addends are the scatter-add's own;
only their order changes. The operands are built block by block and never
exist whole.

Which backward runs is decided at trace time from the table's shape alone
(:func:`grad_path`), and a trace names it: ``moolib.embed.grad.contract``
or ``moolib.embed.grad.scatter``; the forward is ``moolib.embed.lookup``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..parallel.mesh import pvary_if_needed

__all__ = ["embed_lookup", "grad_path"]

# Readings on one TPU v5e (PERF.md, Findings "PR 27"), 17.2M lookups of
# [5976, 16]. The scatter-add ran 6.75 ns an update (116 ms); the product
# read 120 TFLOP/s (27.5 ms for 2 * rows * width FLOPs a lookup): they meet
# at 405,000 cells.
CONTRACT_MAX_CELLS = 400_000
# LO 128 / 256 / 384 / 512 read 36.9 / 34.2 / 34.1 / 36.2 ms at three
# trailing positions a block; at LO 256, blocks of 1 / 3 / 7 / 21 / 79
# positions of 10,368 lookups read 34.5 / 34.2 / 27.5 / 27.4 / 33.9 ms.
LO = 256
BLOCK = 81_920
LANES = 128  # the narrowest leading axis worth keeping minor


def grad_path(table_shape) -> str:
    """``"contract"`` or ``"scatter"``: how the gradient of a table of this
    shape is computed. Rows times width is what a lookup costs the
    contraction; the scatter-add costs by the update."""
    rows, width = table_shape
    return "contract" if rows * width <= CONTRACT_MAX_CELLS else "scatter"


def embed_lookup(table, ids, dtype=None):
    """``table[ids].astype(dtype)`` for integer ``ids`` in ``[0, rows)``.

    The value is bitwise the plain gather's. The gradient with respect to
    ``table`` is the contraction above when :func:`grad_path` says so and the
    gather's own otherwise, accumulated in float32 from the cotangent in the
    dtype it arrives in."""
    # Inside a shard_map that splits the ids a replicated table varies as
    # they do from here on, as under the plain gather: JAX then sums its
    # gradient over those axes itself.
    for axis in jax.typeof(ids).vma:
        table = pvary_if_needed(table, axis)
    return _lookup(table, ids, jnp.dtype(dtype or table.dtype))


def _gather(table, ids, dtype):
    return jnp.take(table, ids, axis=0).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(table, ids, dtype):
    with jax.named_scope("moolib.embed.lookup"):
        return _gather(table, ids, dtype)


def _lookup_fwd(table, ids, dtype):
    return _lookup(table, ids, dtype), (table, ids)


def _lookup_bwd(dtype, res, g):
    table, ids = res  # the table for its shape and dtype only
    path = grad_path(table.shape)
    with jax.named_scope(f"moolib.embed.grad.{path}"):
        if path == "contract":
            d_table = _contract(ids, g, table.shape[0]).astype(table.dtype)
        else:
            (d_table,) = jax.vjp(lambda t: _gather(t, ids, dtype), table)[1](g)
    return d_table, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def _blocks(ids, g):
    """The lookups as ``ids [blocks, k, m]`` and ``g [blocks, k, width, m]``:
    ``k * m <= BLOCK`` lookups a block, ``m`` minor. Any order will do, the
    sum runs over them all. The leading axis of ``ids`` stays whole inside a
    block where it can (``m`` is that axis, ``k`` of the trailing positions
    go with it): XLA lays the cotangent of a convolution with few channels
    out with the batch minor, and this is then the order it lies in memory.
    Otherwise the lookups are flattened and cut, the last block padded with
    zero cotangents, which add nothing."""
    width = g.shape[-1]
    lead = ids.shape[0] if ids.ndim > 1 else 0
    if LANES <= lead <= BLOCK:
        ids = ids.reshape(lead, -1).T
        g = g.reshape(lead, -1, width).transpose(1, 2, 0)
        positions = ids.shape[0]
        k = max(d for d in range(1, BLOCK // lead + 1) if positions % d == 0)
        return ids.reshape(-1, k, lead), g.reshape(-1, k, width, lead)
    m = min(BLOCK, -(-max(ids.size, 1) // LANES) * LANES)
    pad = -ids.size % m
    ids = jnp.pad(ids.reshape(-1), (0, pad))
    g = jnp.pad(g.reshape(-1, width), ((0, pad), (0, 0)))
    return (ids.reshape(-1, 1, m),
            g.reshape(-1, 1, m, width).transpose(0, 1, 3, 2))


def _contract(ids, g, rows):
    """``zeros([rows, width]).at[ids].add(g)`` as a blocked product."""
    width = g.shape[-1]
    his = -(-rows // LO)
    acc_dtype = jnp.promote_types(g.dtype, jnp.float32)
    # bfloat16 operands in one pass; anything wider is not rounded below
    # the dtype it arrives in
    precision = None if g.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST
    hi_range = jnp.arange(his, dtype=jnp.int32)[:, None, None]
    lo_range = jnp.arange(LO, dtype=jnp.int32)

    def add_block(acc, block):
        ids_b, g_b = block  # [k, m], [k, width, m]
        hi, lo = ids_b // LO, ids_b % LO
        left = jnp.where(
            (hi == hi_range)[:, None], g_b.transpose(1, 0, 2), 0
        )  # [H, width, k, m]
        right = (lo[..., None] == lo_range).astype(g_b.dtype)  # [k, m, LO]
        return acc + jnp.einsum(
            "hdkm,kml->hdl", left, right, precision=precision,
            preferred_element_type=acc_dtype,
        ), None

    zero = jnp.zeros((his, width, LO), acc_dtype)
    for axis in jax.typeof(g).vma:  # the sum varies as the cotangent does
        zero = pvary_if_needed(zero, axis)
    acc, _ = jax.lax.scan(add_block, zero, _blocks(ids.astype(jnp.int32), g))
    return acc.transpose(0, 2, 1).reshape(his * LO, width)[:rows]
