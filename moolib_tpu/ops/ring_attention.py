"""Ring attention: exact attention over sequences sharded across devices.

The reference has no long-context machinery (SURVEY.md §5) — this is new
TPU-first scope, the multi-chip half of the long-context story. The design
is the ring-attention construction (blockwise attention + ring-rotated
key/value shards): every device holds one sequence shard [B, H, T_local, D];
at each of the ``sp`` axis' N steps it folds the currently-held K/V shard
into its online-softmax state (the combine math shared with
:mod:`moolib_tpu.ops.attention`) and forwards the shard to its ring
neighbor with ``lax.ppermute``. After N steps every query row has attended
to the full global sequence, with O(T_local) memory per device and
communication overlapping compute under XLA's async collectives.

Differentiability comes for free: the loop is a ``lax.scan`` and
``ppermute`` transposes to a ppermute, so ``jax.grad`` through ring
attention is itself a ring collective — no custom VJP needed.

``ring_attention`` must be called INSIDE ``shard_map`` (it uses
``axis_index``); ``sequence_sharded_attention`` is the outside-jit
convenience wrapper that builds the shard_map over a mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .attention import _finalize, _online_block, _scale
from ..parallel.mesh import pvary_if_needed

__all__ = [
    "ring_attention",
    "sequence_sharded_attention",
    "zigzag_order",
    "zigzag_ring_attention",
    "zigzag_sharded_attention",
]


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
):
    """Exact global attention over per-device sequence shards.

    Args (all per-device shards, global sequence = concat over ``axis_name``
    in axis-index order):
      q, k, v: [B, H, T_local, D]
      segment_ids: [B, T_local] query segment ids (optional)
      kv_segment_ids: [B, T_local] key segment ids (defaults to segment_ids)

    Returns [B, H, T_local, D] — this device's rows of the global result.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, T, D = q.shape
    qf = _scale(q.astype(jnp.float32))

    seg_q = segment_ids
    seg_k0 = segment_ids if kv_segment_ids is None else kv_segment_ids
    if seg_q is None and kv_segment_ids is not None:
        raise ValueError(
            "kv_segment_ids without segment_ids: key segments would be "
            "silently ignored — pass both (or segment_ids alone)"
        )
    # Always carry a seg tensor so the scan structure is static; a constant
    # zero tensor when segments are unused.
    carry_seg = (
        seg_k0 if seg_k0 is not None else jnp.zeros((B, T), jnp.int32)
    )
    use_seg = seg_q is not None

    perm = [(j, (j + 1) % n) for j in range(n)]
    qpos = idx * T + jnp.arange(T)  # global positions of local q rows

    def step(carry, i):
        kb, vb, segb, m, l, acc = carry
        # The shard we hold at step i originated on device (idx - i) mod n.
        src = (idx - i) % n

        def fold(mla):
            m, l, acc = mla
            bias = None
            if causal:
                kpos = src * T + jnp.arange(T)
                bias = jnp.where(
                    qpos[:, None] >= kpos[None, :], 0.0, -1e30
                )  # [T, T]
            if use_seg:
                same = seg_q[:, None, :, None] == segb[:, None, None, :]
                seg_bias = jnp.where(same, 0.0, -1e30)
                bias = seg_bias if bias is None else bias + seg_bias
            return _online_block(
                qf, kb.astype(jnp.float32), vb.astype(jnp.float32),
                bias, m, l, acc,
            )

        if causal:
            # Causal step skipping: a shard from a strictly-later device is
            # fully masked (min kpos = src*T > max qpos = idx*T + T - 1), so
            # folding it is pure wasted FLOPs — skip via cond. The K/V
            # rotation below does NOT depend on the fold, so XLA can run the
            # ring ahead of compute and device idx pays for only idx+1 folds
            # (~2x average causal throughput; the last ring device still
            # folds all n shards, so perfectly load-balanced causal sharding
            # would need striped token layouts).
            m, l, acc = jax.lax.cond(
                src <= idx, fold, lambda mla: mla, (m, l, acc)
            )
        else:
            m, l, acc = fold((m, l, acc))
        # Rotate K/V (and key segments) one step around the ring.
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        segb = jax.lax.ppermute(segb, axis_name, perm)
        return (kb, vb, segb, m, l, acc), None

    # Fresh constants are 'unvarying' over the manual mesh axis; the scan
    # body makes them device-varying, so the initial carry must be marked
    # varying too (shard_map vma typing).
    def pv(x):  # no-op if already varying (e.g. real segment-id shards)
        return pvary_if_needed(x, axis_name)

    m0 = pv(jnp.full((B, H, T), -jnp.inf, jnp.float32))
    l0 = pv(jnp.zeros((B, H, T), jnp.float32))
    a0 = pv(jnp.zeros((B, H, T, D), jnp.float32))
    (kb, vb, segb, m, l, acc), _ = jax.lax.scan(
        step, (k, v, pv(carry_seg), m0, l0, a0), jnp.arange(n)
    )
    return _finalize(m, l, acc, v.dtype)


def sequence_sharded_attention(
    mesh: Mesh,
    q,
    k,
    v,
    axis_name: str = "sp",
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
):
    """Ring attention over globally-shaped arrays: shards [B, H, T, D] along
    T over ``axis_name`` of ``mesh``, runs :func:`ring_attention` inside
    shard_map, returns the globally-shaped result."""
    seq_spec = P(None, None, axis_name, None)
    seg_spec = P(None, axis_name)

    if segment_ids is None:

        def f(q, k, v):
            return ring_attention(q, k, v, axis_name=axis_name, causal=causal)

        return jax.jit(
            jax.shard_map(
                f,
                mesh=mesh,
                in_specs=(seq_spec, seq_spec, seq_spec),
                out_specs=seq_spec,
            )
        )(q, k, v)

    def f(q, k, v, seg):
        return ring_attention(
            q, k, v, axis_name=axis_name, causal=causal,
            segment_ids=seg, kv_segment_ids=seg,
        )

    return jax.jit(
        jax.shard_map(
            f,
            mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec, seg_spec),
            out_specs=seq_spec,
        )
    )(q, k, v, segment_ids)


# ---------------------------------------------------------------------------
# Zigzag (striped) causal ring attention: load-balanced sequence parallelism.
#
# Plain ring attention with contiguous shards is causally imbalanced: device
# n-1's queries attend to every shard (n folds) while device 0's attend only
# to their own — wall-clock is set by the busiest device even with step
# skipping. The zigzag layout splits the sequence into 2n chunks and gives
# device d chunks (d, 2n-1-d); for every (q-chunk a, k-chunk b) pair the
# causal decision is chunk-level (a > b: full fold, a == b: triangle,
# a < b: skip), and each device ends up with exactly 2n+1 allowed chunk
# folds per full ring pass — identical on every device. This is the
# "striped attention" / context-parallel layout used for long-context
# training; no reference counterpart (the reference has no attention at
# all, SURVEY.md §5).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _zigzag_order_cached(n: int, seq_len: int):
    if seq_len % (2 * n) != 0:
        raise ValueError(f"seq_len {seq_len} not divisible by 2n={2 * n}")
    tc = seq_len // (2 * n)
    chunks = []
    for d in range(n):
        chunks += [d, 2 * n - 1 - d]
    perm = np.concatenate([np.arange(c * tc, (c + 1) * tc) for c in chunks])
    perm.setflags(write=False)
    inv = np.argsort(perm)
    inv.setflags(write=False)
    return perm, inv


def zigzag_order(n: int, seq_len: int) -> np.ndarray:
    """Gather indices reordering a global [.., S, ..] sequence so contiguous
    n-way sharding gives device d chunks (d, 2n-1-d). Invert with argsort."""
    return _zigzag_order_cached(n, seq_len)[0]


def zigzag_ring_attention(
    q,
    k,
    v,
    axis_name: str = "sp",
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
):
    """Causal attention over zigzag-laid-out per-device shards.

    Per-device inputs are [B, H, 2*Tc, D]: rows [:Tc] are global chunk
    ``idx`` and rows [Tc:] chunk ``2n-1-idx`` (produce the layout with
    :func:`zigzag_order`; :func:`zigzag_sharded_attention` does it for you).
    Causality is implicit in the layout — there is no ``causal=False``.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, T2, D = q.shape
    if T2 % 2 != 0:
        raise ValueError("zigzag shard length must be even (two chunks)")
    tc = T2 // 2
    qf = _scale(q.astype(jnp.float32))

    if segment_ids is None and kv_segment_ids is not None:
        raise ValueError("kv_segment_ids without segment_ids")
    use_seg = segment_ids is not None
    carry_seg = (
        kv_segment_ids if kv_segment_ids is not None else segment_ids
    )
    if carry_seg is None:
        carry_seg = jnp.zeros((B, T2), jnp.int32)

    perm = [(j, (j + 1) % n) for j in range(n)]
    tri = jnp.where(
        jnp.arange(tc)[:, None] >= jnp.arange(tc)[None, :], 0.0, -1e30
    )  # [Tc, Tc] causal triangle, valid whenever q-chunk == k-chunk

    def fold_chunk(qc, kc, vc, segq_c, segk_c, a, b, mla):
        """Fold k-chunk ``b`` into q-chunk ``a``'s online-softmax state.
        Chunk-level causality: a < b skip, a == b triangle, a > b full."""

        def seg_bias():
            same = segq_c[:, None, :, None] == segk_c[:, None, None, :]
            return jnp.where(same, 0.0, -1e30)

        def do_skip(mla):
            return mla

        def do_tri(mla):
            bias = tri + seg_bias() if use_seg else tri
            return _online_block(qc, kc, vc, bias, *mla)

        def do_full(mla):
            bias = seg_bias() if use_seg else None
            return _online_block(qc, kc, vc, bias, *mla)

        branch = jnp.clip(jnp.sign(a - b) + 1, 0, 2)
        return jax.lax.switch(branch, [do_skip, do_tri, do_full], mla)

    qc0, qc1 = qf[..., :tc, :], qf[..., tc:, :]
    a0, a1 = idx, 2 * n - 1 - idx
    seg_local = segment_ids if use_seg else jnp.zeros((B, T2), jnp.int32)
    sq0, sq1 = seg_local[:, :tc], seg_local[:, tc:]

    def step(carry, i):
        kb, vb, segb, mla0, mla1 = carry
        src = (idx - i) % n
        b0, b1 = src, 2 * n - 1 - src
        kc0, kc1 = kb[..., :tc, :], kb[..., tc:, :]
        vc0, vc1 = vb[..., :tc, :], vb[..., tc:, :]
        sk0, sk1 = segb[:, :tc], segb[:, tc:]
        kc0, kc1 = kc0.astype(jnp.float32), kc1.astype(jnp.float32)
        vc0, vc1 = vc0.astype(jnp.float32), vc1.astype(jnp.float32)
        mla0 = fold_chunk(qc0, kc0, vc0, sq0, sk0, a0, b0, mla0)
        mla0 = fold_chunk(qc0, kc1, vc1, sq0, sk1, a0, b1, mla0)
        mla1 = fold_chunk(qc1, kc0, vc0, sq1, sk0, a1, b0, mla1)
        mla1 = fold_chunk(qc1, kc1, vc1, sq1, sk1, a1, b1, mla1)
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        segb = jax.lax.ppermute(segb, axis_name, perm)
        return (kb, vb, segb, mla0, mla1), None

    def pv(x):
        return pvary_if_needed(x, axis_name)

    def zero_mla():
        return (
            pv(jnp.full((B, H, tc), -jnp.inf, jnp.float32)),
            pv(jnp.zeros((B, H, tc), jnp.float32)),
            pv(jnp.zeros((B, H, tc, D), jnp.float32)),
        )

    (kb, vb, segb, mla0, mla1), _ = jax.lax.scan(
        step, (k, v, pv(carry_seg), zero_mla(), zero_mla()), jnp.arange(n)
    )
    out0 = _finalize(*mla0, v.dtype)
    out1 = _finalize(*mla1, v.dtype)
    return jnp.concatenate([out0, out1], axis=-2)


@functools.lru_cache(maxsize=16)
def _zigzag_jitted(mesh: Mesh, axis_name: str, use_seg: bool):
    """Memoized jitted shard_map wrapper — a fresh jit per call would
    retrace/recompile every training step."""
    seq_spec = P(None, None, axis_name, None)
    seg_spec = P(None, axis_name)
    if not use_seg:

        def f(q, k, v):
            return zigzag_ring_attention(q, k, v, axis_name=axis_name)

        return jax.jit(
            jax.shard_map(
                f, mesh=mesh,
                in_specs=(seq_spec, seq_spec, seq_spec),
                out_specs=seq_spec,
            )
        )

    def f(q, k, v, seg):
        return zigzag_ring_attention(
            q, k, v, axis_name=axis_name, segment_ids=seg,
            kv_segment_ids=seg,
        )

    return jax.jit(
        jax.shard_map(
            f, mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec, seg_spec),
            out_specs=seq_spec,
        )
    )


def zigzag_sharded_attention(
    mesh: Mesh,
    q,
    k,
    v,
    axis_name: str = "sp",
    segment_ids: Optional[jax.Array] = None,
):
    """Causal zigzag ring attention over globally-shaped arrays: permutes
    the sequence into zigzag order, shards [B, H, S, D] along S, runs
    :func:`zigzag_ring_attention` inside shard_map, and un-permutes.

    Convenience API for globally-shaped data: the permute/un-permute gathers
    materialize full [B, H, S, D] arrays. Training loops at scale should
    instead keep data in zigzag layout end to end (apply
    :func:`zigzag_order` once at the data layout level) and call
    :func:`zigzag_ring_attention` inside their own shard_map.
    """
    n = mesh.shape[axis_name]
    S = q.shape[-2]
    perm, inv = _zigzag_order_cached(n, S)
    qz, kz, vz = q[..., perm, :], k[..., perm, :], v[..., perm, :]
    fn = _zigzag_jitted(mesh, axis_name, segment_ids is not None)
    if segment_ids is None:
        out = fn(qz, kz, vz)
    else:
        out = fn(qz, kz, vz, segment_ids[..., perm])
    return out[..., inv, :]
