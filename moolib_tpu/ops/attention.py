"""Attention ops: dense oracle, memory-efficient blockwise, pallas flash.

The reference has NO attention/long-context machinery at all (verified in
SURVEY.md §5: no ring attention, no sequence parallelism anywhere in the
tree) — this module is new TPU-first scope, the single-chip half of the
long-context story (the multi-chip half is
:mod:`moolib_tpu.ops.ring_attention`, which reuses the online-softmax
combine defined here).

Three implementations, one contract ``[B, H, T, D] -> [B, H, T, D]``:

- :func:`dense_attention` — materializes the [Tq, Tk] score matrix; the
  correctness oracle and the fast path for short sequences.
- :func:`blockwise_attention` — Rabe-Staats/FlashAttention math in pure JAX:
  a ``lax.scan`` over key/value blocks carrying the online-softmax state
  (m, l, acc), so peak memory is O(T·block) instead of O(T²) and reverse-mode
  differentiation works out of the box (scan transposes cleanly).
- :func:`flash_attention` — pallas TPU kernels for BOTH passes: forward
  (grid over (batch·heads, q-blocks, k-blocks), f32 VMEM accumulators,
  online softmax, per-row log-sum-exp emitted for the backward) and the
  FlashAttention backward (ONE kernel that rebuilds P from the saved lse
  once a visible tile and makes dQ, dK and dV from it — no second
  softmax, no O(T²) residuals), O(T) memory end to end with causal block
  skipping in both kernels.

All three support causal masking and ``segment_ids`` (attention is blocked
across segment boundaries — used by the transformer agent to stop attention
across episode resets inside an unroll), a causal ``window`` (query i sees
key j only while ``i - j < window``) and grouped heads: ``k``/``v`` may
carry fewer heads than ``q`` (``H % Hkv == 0``; query head h reads key/value
head ``h // (H // Hkv)``), and no backend materialises the repeats. The
flash kernels visit only the key blocks a window can reach, and skip the
tiles that lie wholly above the diagonal or wholly in another segment.

The value head may be narrower or wider than the query/key head: ``q`` and
``k`` are ``[.., T, D]``, ``v`` and the output ``[.., T, Dv]`` (latent
attention with a rotary part on its queries and keys only). ``scale``
multiplies the scores; None is ``D ** -0.5``.

**One softmax over two key sets** (chunk-summary attention: a query reads
the positions of its own window and, of every earlier window, one summary
key and value a chunk). Two things make it two calls of what is here:

- ``return_lse=True``: a call returns ``(out, lse)``, ``lse`` [B, H, Tq]
  float32 the log of the row's sum of ``e^score`` over the keys it saw
  (-1e30 and a row of zeros where it saw none), *differentiable*: since
  ``d lse_i / d s_ij = p_ij``, its cotangent is taken off the backward
  pass's ``delta`` and the flash kernels are the same two.
  :func:`merge_attention` then gives ``(o1 e^lse1 + o2 e^lse2) / (e^lse1 +
  e^lse2)``, the one softmax over both sets, exactly. Where nothing asks
  for ``lse`` a flash call traces the program it always traced: that is
  why ``_flash_attention_lse`` is a second ``custom_vjp`` beside
  ``_flash_attention`` over the same two kernels, and the two change
  together (an operand, a tile rule or a residual added to one's forward
  and backward goes into the other's).
- ``rank_bits=b`` (with ``causal=False`` and both id arrays): an id is
  two numbers, ``group << b | rank``, and a query sees a key iff the
  groups are equal and the key's rank is *strictly lower* than its own:
  group = episode, rank = window says "the summaries of my episode's
  earlier windows". The flash kernels skip a tile whose blocks' groups do
  not meet or whose least key rank is not under the largest query rank
  (ids that never decrease along an axis, in group or in rank), so the
  summaries of windows not yet past cost nothing.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import global_telemetry

__all__ = [
    "dense_attention",
    "blockwise_attention",
    "flash_attention",
    "merge_attention",
    "resolve_backend",
    "attention",
    "KEEP_CORES",
    "keeping_cores",
]

_NEG_INF = -1e30


def _scale(q, scale=None):
    if scale is None:
        return q / np.sqrt(q.shape[-1])
    return q * scale


def _check_window(window, causal: bool):
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and at least one key"
        )


def _group_heads(q, k):
    """q [B, H, Tq, D] as [B, Hkv, G, Tq, D] beside k [B, Hkv, Tk, D]:
    grouped heads are a reshape of the queries, never a repeat of the
    keys."""
    B, H, Tq, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv:
        raise ValueError(
            f"{H} query heads do not split over {Hkv} key/value heads"
        )
    return q.reshape(B, Hkv, H // Hkv, Tq, D)


def _same_segment(seg_q, seg_k, rank_bits=None):
    """Which (query, key) pairs the ids allow, broadcasting ``seg_q``
    against ``seg_k``: equal ids, or, with ``rank_bits``, an equal group
    and a key of strictly lower rank (see the module docstring)."""
    if rank_bits is None:
        return seg_q == seg_k
    low = (1 << rank_bits) - 1
    same = (seg_q >> rank_bits) == (seg_k >> rank_bits)
    return jnp.logical_and(same, (seg_k & low) < (seg_q & low))


def _mask_bias(Tq: int, Tk: int, causal: bool, seg_q, seg_k, q_offset=0,
               window=None, rank_bits=None):
    """[.., Tq, Tk] additive bias: 0 where allowed, -inf where masked.

    ``q_offset`` is the absolute position of q row 0 relative to k row 0
    (used by blockwise/ring variants where q and k are different blocks).
    """
    bias = None
    if causal:
        qpos = jnp.arange(Tq)[:, None] + q_offset
        kpos = jnp.arange(Tk)[None, :]
        seen = qpos >= kpos
        if window is not None:
            seen = jnp.logical_and(seen, qpos - kpos < window)
        bias = jnp.where(seen, 0.0, _NEG_INF)
    if seg_q is not None:
        same = _same_segment(
            seg_q[..., :, None], seg_k[..., None, :], rank_bits
        )
        seg_bias = jnp.where(same, 0.0, _NEG_INF)
        bias = seg_bias if bias is None else bias + seg_bias
    return bias


def _check_ranks(rank_bits, causal: bool, segment_ids, kv_segment_ids):
    if rank_bits is not None and (
        causal or segment_ids is None or kv_segment_ids is None
    ):
        raise ValueError(
            "rank_bits orders keys by their ids' low bits: it needs "
            "segment_ids, kv_segment_ids and causal=False"
        )


def dense_attention(
    q,
    k,
    v,
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    rank_bits: Optional[int] = None,
    return_lse: bool = False,
):
    """Oracle attention. q [B, H, Tq, D], k [B, Hkv, Tk, D], v
    [B, Hkv, Tk, Dv], segment_ids [B, Tq] / kv_segment_ids [B, Tk]
    (defaults to segment_ids). ``rank_bits``, ``return_lse``: the module
    docstring."""
    _check_window(window, causal)
    _check_ranks(rank_bits, causal, segment_ids, kv_segment_ids)
    shape = q.shape[:-1] + v.shape[-1:]
    q = _group_heads(_scale(q.astype(jnp.float32), scale), k)
    k = k.astype(jnp.float32)
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q, k)
    seg_q = seg_k = None
    if segment_ids is not None:
        kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
        seg_q = segment_ids[:, None, None, :]  # [B, 1, 1, Tq]
        seg_k = kv_seg[:, None, None, :]
    bias = _mask_bias(
        q.shape[-2], k.shape[-2], causal, seg_q, seg_k, window=window,
        rank_bits=rank_bits,
    )
    if bias is not None:
        scores = scores + bias
    if return_lse:
        # a row that sees no key: zeros and the statistic of an empty set
        seen = jnp.max(scores, axis=-1) > _NEG_INF / 2
        lse = jnp.where(
            seen, jax.nn.logsumexp(scores, axis=-1), _NEG_INF
        )
        w = jnp.where(seen[..., None], jnp.exp(scores - lse[..., None]), 0.0)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", w, v.astype(jnp.float32))
        return (out.reshape(shape).astype(v.dtype),
                lse.reshape(shape[:-1]))
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum(
        "bhgqk,bhkd->bhgqd", w, v.astype(jnp.float32)
    ).reshape(shape).astype(v.dtype)


def _online_block(q, k, v, bias, m, l, acc):
    """One online-softmax step: fold the (q, k-block) scores into the
    running (m, l, acc) state. Shapes: q [.., Tq, D], k/v [.., Tk, D],
    m/l [.., Tq], acc [.., Tq, D]; all f32."""
    s = jnp.einsum("...qd,...kd->...qk", q, k)
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # Rows whose max is still at the mask floor are fully masked: treat as
    # -inf (same `> _NEG_INF/2` rule as the pallas kernel, so every online-
    # softmax variant yields ZEROS for fully-masked rows instead of the
    # finite-bias uniform degeneracy) and guard the exp shift.
    masked = m_new <= _NEG_INF / 2
    shift = jnp.where(masked, 0.0, m_new)
    p = jnp.where(
        masked[..., None], 0.0, jnp.exp(s - shift[..., None])
    )
    scale_old = jnp.where(
        m > _NEG_INF / 2, jnp.exp(m - shift), jnp.zeros_like(m)
    )
    l_new = l * scale_old + jnp.sum(p, axis=-1)
    acc_new = acc * scale_old[..., None] + jnp.einsum(
        "...qk,...kd->...qd", p, v
    )
    return m_new, l_new, acc_new


def _finalize(m, l, acc, dtype):
    # Fully-masked rows (l == 0) return zeros, not NaNs.
    safe_l = jnp.where(l > 0, l, 1.0)
    return (acc / safe_l[..., None]).astype(dtype)


def blockwise_attention(
    q,
    k,
    v,
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_k: int = 512,
    kv_position_offset: int = 0,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    rank_bits: Optional[int] = None,
    return_lse: bool = False,
):
    """Memory-efficient attention: lax.scan over key blocks.

    ``kv_position_offset``: absolute position of k row 0 relative to q row 0
    (negative when keys precede queries — the ring-attention case).
    ``rank_bits``, ``return_lse``: the module docstring.
    """
    _check_window(window, causal)
    _check_ranks(rank_bits, causal, segment_ids, kv_segment_ids)
    orig_dtype = v.dtype
    # Grouped heads: the G query heads of one key/value head are G more
    # rows of queries against the same keys.
    qf = _group_heads(_scale(q.astype(jnp.float32), scale), k)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    B, _, Tq, D = q.shape
    Dv = v.shape[-1]
    H, G = qf.shape[1:3]
    Tk = k.shape[-2]
    block_k = min(block_k, Tk)
    n_blocks = -(-Tk // block_k)
    pad = n_blocks * block_k - Tk
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kv_seg = segment_ids if kv_segment_ids is None else kv_segment_ids
    if segment_ids is not None and pad:
        # Padded keys get an impossible segment id so they never match.
        kv_seg = jnp.pad(kv_seg, ((0, 0), (0, pad)), constant_values=-1)
    elif segment_ids is None and pad:
        # No segments: mask padded keys via a synthetic segment pair.
        segment_ids = jnp.zeros((B, Tq), jnp.int32)
        kv_seg = jnp.pad(
            jnp.zeros((B, Tk), jnp.int32), ((0, 0), (0, pad)),
            constant_values=-1,
        )

    kb = kf.reshape(B, H, n_blocks, block_k, D).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(B, H, n_blocks, block_k, Dv).transpose(2, 0, 1, 3, 4)
    if segment_ids is not None:
        sb = kv_seg.reshape(B, n_blocks, block_k).transpose(1, 0, 2)
    else:
        sb = jnp.zeros((n_blocks, B, 1), jnp.int32)  # unused placeholder

    qpos = jnp.arange(Tq)[:, None] - kv_position_offset

    def step(carry, xs):
        m, l, acc = carry
        ki, kblk, vblk, segk = xs
        bias = None
        if causal:
            kpos = ki * block_k + jnp.arange(block_k)[None, :]
            seen = qpos >= kpos
            if window is not None:
                seen = jnp.logical_and(seen, qpos - kpos < window)
            bias = jnp.where(seen, 0.0, _NEG_INF)  # [Tq, block_k]
        if segment_ids is not None:
            same = _same_segment(
                segment_ids[:, None, None, :, None],
                segk[:, None, None, None, :], rank_bits,
            )  # [B, 1, 1, Tq, block_k]
            seg_bias = jnp.where(same, 0.0, _NEG_INF)
            bias = seg_bias if bias is None else bias + seg_bias
        m, l, acc = _online_block(
            qf, kblk[:, :, None], vblk[:, :, None], bias, m, l, acc
        )
        return (m, l, acc), None

    m0 = jnp.full((B, H, G, Tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, H, G, Tq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(n_blocks), kb, vb, sb)
    )
    out = _finalize(m, l, acc, orig_dtype).reshape(q.shape[:-1] + (Dv,))
    if not return_lse:
        return out
    lse = jnp.where(
        l > 0, jnp.where(m > _NEG_INF / 2, m, 0.0)
        + jnp.log(jnp.where(l > 0, l, 1.0)), _NEG_INF,
    )
    return out, lse.reshape(q.shape[:-1])


# ---------------------------------------------------------------------------
# Pallas flash-attention kernels (forward, backward)
# ---------------------------------------------------------------------------
#
# Mosaic layout rules the kernels are written to (every value is 2-D):
#
# - a per-row statistic of a [rows, cols] score tile (running max, sum, lse,
#   delta, the row axis' segment ids) lives LANE-REPLICATED as [rows, 128]
#   — in VMEM scratch, in the kernel and in HBM — and is widened or narrowed
#   to a tile's column count with :func:`_lanes`; a 1-D [rows] vector cannot
#   be turned back into a column on the chip (lane -> sublane relayout);
# - a per-column quantity is a [1, cols] row, broadcast along sublanes;
# - q·kᵀ is an NT ``dot_general`` (contract both minor dims), never an
#   in-kernel transpose. The backward kernel works on the TRANSPOSED tile
#   sᵀ = k·qᵀ [block_k, block_q] so that its products pᵀ·dO and dsᵀ·q are
#   plain NN matmuls; per-query statistics are rows there and the key
#   segment ids are the lane-replicated columns;
# - the backward kernel's grid keeps a key block resident (dk, dv in
#   scratch) and walks the query blocks under it, so dq, which belongs to
#   the query blocks, stays in VMEM for a grid row's whole walk: the
#   float32 [G * Tq, D] of the G query heads that share the row's key/value
#   head, scaled, cast and written once at the row's last step. Its size
#   is the shape's (:func:`_dq_resident_bytes`, which also sets the
#   kernel's ``vmem_limit_bytes``); heads that do not fit together are
#   spread over more rows (:func:`_dq_passes`).

_LANES = 128
_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _lanes(x, n: int):
    """Lane-replicated [rows, 128] -> [rows, n]."""
    reps, rem = divmod(n, _LANES)
    if rem == 0:
        return jnp.tile(x, (1, reps))
    if reps == 0:
        return x[:, :n]
    raise ValueError(
        f"flash attention tile width {n} must be below or a multiple of "
        f"{_LANES}"
    )


def _tile_mask(causal, q_axis, q_start, k_start, seg_rows, seg_cols,
               window=None, rank_bits=None):
    """Visibility of one score tile — the ONE definition shared by the
    forward and the backward kernel, so the masks can never diverge.
    ``seg_rows`` [rows, cols] / ``seg_cols`` [1, cols] are the segment ids
    of the tile's row and column axes; ``q_axis`` says which axis carries
    the queries (0 for s, 1 for the backward kernel's sᵀ)."""
    if rank_bits is None:
        mask = seg_rows == seg_cols
    else:
        seg_q, seg_k = (
            (seg_rows, seg_cols) if q_axis == 0 else (seg_cols, seg_rows)
        )
        mask = _same_segment(seg_q, seg_k, rank_bits)
    if causal:
        qpos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, mask.shape, q_axis
        )
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, mask.shape, 1 - q_axis
        )
        mask = jnp.logical_and(mask, qpos >= kpos)
        if window is not None:
            mask = jnp.logical_and(mask, qpos - kpos < window)
    return mask


class _Tiles(NamedTuple):
    """The static geometry of one flash call, and with it which tiles a
    kernel visits at all. Without a window every kernel walks the whole
    other axis (and skips what the diagonal hides); with one, only the
    ``n_kw`` key blocks a query block can reach (``n_qw`` query blocks
    that can reach a key block), counted here from the block sizes."""

    causal: bool
    window: Optional[int]
    block_q: int
    block_k: int
    n_q: int
    n_k: int
    H: int  # query heads
    Hkv: int  # key/value heads
    scale: float  # of the scores
    rank_bits: Optional[int] = None  # ids as group and rank

    @property
    def G(self) -> int:
        return self.H // self.Hkv

    def first_k(self, qi):
        """First key block the window lets query block ``qi`` see (python
        ints or traced scalars alike)."""
        if self.window is None:
            return 0 * qi
        lo = qi * self.block_q - (self.window - 1)
        lo = max(lo, 0) if isinstance(lo, int) else jnp.maximum(lo, 0)
        return lo // self.block_k

    def first_q(self, kj):
        """First query block that can see key block ``kj``."""
        if self.window is None:
            return 0 * kj
        return (kj * self.block_k) // self.block_q

    def k_block(self, qi, j):
        """Key block at step ``j`` of query block ``qi``'s walk, held
        inside the sequence (a step past it is skipped by ``visible``)."""
        return jnp.minimum(self.first_k(qi) + j, self.n_k - 1)

    def q_block(self, kj, step):
        """Query block at ``step`` of key block ``kj``'s walk over its
        ``G`` query heads, ``n_qw`` blocks a head."""
        return jnp.minimum(self.first_q(kj) + step % self.n_qw, self.n_q - 1)

    @property
    def n_kw(self) -> int:
        if self.window is None:
            return self.n_k
        return max(
            min(self.n_k - 1, (qi * self.block_q + self.block_q - 1)
                // self.block_k) - self.first_k(qi) + 1
            for qi in range(self.n_q)
        )

    @property
    def n_qw(self) -> int:
        if self.window is None:
            return self.n_q
        reach = self.block_k - 1 + self.window - 1
        return max(
            min(self.n_q - 1, (kj * self.block_k + reach) // self.block_q)
            - self.first_q(kj) + 1
            for kj in range(self.n_k)
        )

    def visible(self, qi, ki, q_rng, k_rng, b):
        """Whether tile (query block ``qi``, key block ``ki``) of batch row
        ``b`` holds anything: not wholly above the diagonal, not wholly
        out of the window, not wholly in another segment (``q_rng`` /
        ``k_rng``: the least and largest segment id of every block, in
        SMEM), and inside the sequence (a window's walk may step past the
        last block)."""
        lo_q = q_rng[(b * self.n_q + qi) * 2]
        hi_q = q_rng[(b * self.n_q + qi) * 2 + 1]
        lo_k = k_rng[(b * self.n_k + ki) * 2]
        hi_k = k_rng[(b * self.n_k + ki) * 2 + 1]
        if self.rank_bits is None:
            seen = jnp.logical_and(lo_k <= hi_q, hi_k >= lo_q)
        else:
            # Ids that never decrease along either axis, in group and in
            # rank: a block's least id holds its least group and its least
            # rank, its largest id the largest of both. Groups that meet,
            # and a key's rank under some query's.
            bits, low = self.rank_bits, (1 << self.rank_bits) - 1
            seen = jnp.logical_and(
                jnp.logical_and((lo_k >> bits) <= (hi_q >> bits),
                                (hi_k >> bits) >= (lo_q >> bits)),
                (lo_k & low) < (hi_q & low),
            )
        if self.causal:
            q_last = qi * self.block_q + self.block_q - 1
            seen = jnp.logical_and(seen, ki * self.block_k <= q_last)
        if self.window is not None:
            k_last = ki * self.block_k + self.block_k - 1
            seen = jnp.logical_and(
                seen, k_last >= qi * self.block_q - (self.window - 1)
            )
        return seen


def _flash_kernel(q_rng, k_rng, q_ref, k_ref, v_ref, seg_q_ref, seg_k_ref,
                  o_ref, lse_ref, m_sc, l_sc, acc_sc, *, t: _Tiles):
    """Grid: (B*H, n_q, n_kw); the k-axis is the sequential ('arbitrary')
    dimension carrying the online-softmax state in VMEM scratch. q/k/v
    blocks arrive pre-staged by BlockSpec. Also emits the per-row
    log-sum-exp (lse) the backward kernel rebuilds P from."""
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ki = t.first_k(qi) + j
    dv = v_ref.shape[-1]  # the value head's size, and the output's
    block_k = t.block_k

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, -jnp.inf)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # Block skipping: a tile the diagonal, the window or the segments hide
    # wholly costs no MXU work (causal alone roughly halves the FLOPs).
    @pl.when(jnp.logical_and(
        ki < t.n_k, t.visible(qi, ki, q_rng, k_rng, b // t.H)
    ))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * t.scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
        mask = _tile_mask(
            t.causal, 0, qi * t.block_q, ki * block_k,
            _lanes(seg_q_ref[0], block_k), seg_k_ref[0], t.window,
            t.rank_bits,
        )
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        shift = jnp.where(m_new > _NEG_INF / 2, m_new, 0.0)
        p = jnp.exp(s - _lanes(shift, block_k))
        scale_old = jnp.where(
            m_prev > _NEG_INF / 2, jnp.exp(m_prev - shift), 0.0
        )
        m_sc[...] = m_new
        l_sc[...] = l_sc[...] * scale_old + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * _lanes(scale_old, dv) + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(j == t.n_kw - 1)
    def _done():
        l = l_sc[...]
        safe_l = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_sc[...] / _lanes(safe_l, dv)).astype(
            o_ref.dtype
        )
        # lse = m + log(l). Fully-masked rows get the finite sentinel
        # +1e30 so the backward's exp(s - lse) underflows to exactly 0
        # (s is at most -1e30 there) without an isfinite select.
        m = m_sc[...]
        shift = jnp.where(m > _NEG_INF / 2, m, 0.0)
        lse_ref[0] = jnp.where(l > 0, shift + jnp.log(safe_l), -_NEG_INF)


def _row_form(x):
    """[B, T] per-position values -> [B, 1, T]: a (1, 1, block) BlockSpec
    then delivers a [1, block] row (the middle singleton satisfies the
    sublane rule as a full dimension)."""
    return x[:, None, :]


def _col_form(x):
    """[B, T] per-position values -> lane-replicated [B, T, 128]: a
    (1, block, 128) BlockSpec delivers the column :func:`_lanes` widens."""
    return jnp.broadcast_to(x[:, :, None], x.shape + (_LANES,))


def _check_blocks(Tq, Tk, block_q, block_k):
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if Tq % block_q or Tk % block_k:
        raise ValueError(
            f"sequence lengths ({Tq}, {Tk}) must be multiples of the block "
            f"sizes ({block_q}, {block_k})"
        )
    return block_q, block_k


def _tiles(q, k, causal, window, block_q, block_k, scale=None,
           rank_bits=None) -> _Tiles:
    _, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"{H} query heads do not split over {Hkv} key/value heads"
        )
    block_q, block_k = _check_blocks(Tq, Tk, block_q, block_k)
    return _Tiles(causal, window, block_q, block_k, Tq // block_q,
                  Tk // block_k, H, Hkv,
                  1.0 / np.sqrt(D) if scale is None else scale, rank_bits)


def _block_ranges(seg, n_blocks: int):
    """[B, T] segment ids -> flat int32 [B * n_blocks * 2]: every block's
    least and largest id, for the kernels' scalar memory."""
    B = seg.shape[0]
    blocks = seg.astype(jnp.int32).reshape(B, n_blocks, -1)
    return jnp.stack(
        [blocks.min(axis=-1), blocks.max(axis=-1)], axis=-1
    ).reshape(-1)


def _kv_row(t: _Tiles, b):
    """Row of the [B*Hkv, T, D] keys that query row ``b`` of [B*H, T, D]
    reads: grouped heads are an index map, not a repeat."""
    return (b // t.H) * t.Hkv + (b % t.H) // t.G


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _flash_forward(q, k, v, seg_q, seg_k, causal, window, block_q, block_k,
                   interpret, scale, rank_bits=None):
    B, H, Tq, D = q.shape
    Dv = v.shape[-1]
    t = _tiles(q, k, causal, window, block_q, block_k, scale, rank_bits)
    block_q, block_k = t.block_q, t.block_k
    Tk = k.shape[-2]
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * t.Hkv, Tk, D)
    vr = v.reshape(B * t.Hkv, Tk, Dv)

    def k_spec(width):
        return pl.BlockSpec(
            (1, block_k, width),
            lambda b, qi, j, *_: (_kv_row(t, b), t.k_block(qi, j), 0),
        )

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, t.n_q, t.n_kw),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, qi, j, *_: (b, qi, 0)),
                k_spec(D),
                k_spec(Dv),
                # Segment ids are per batch row, shared by its H heads.
                pl.BlockSpec(
                    (1, block_q, _LANES),
                    lambda b, qi, j, *_: (b // H, qi, 0),
                ),
                pl.BlockSpec(
                    (1, 1, block_k),
                    lambda b, qi, j, *_: (b // H, 0, t.k_block(qi, j)),
                ),
            ],
            out_specs=[
                pl.BlockSpec(
                    (1, block_q, Dv), lambda b, qi, j, *_: (b, qi, 0)
                ),
                pl.BlockSpec(
                    (1, block_q, _LANES), lambda b, qi, j, *_: (b, qi, 0)
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, Dv), v.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS
        ),
        interpret=interpret,
    )(_block_ranges(seg_q, t.n_q), _block_ranges(seg_k, t.n_k),
      qr, kr, vr, _col_form(seg_q), _row_form(seg_k))
    # The residual keeps one lane: O(T) memory, not O(128 T).
    return out.reshape(B, H, Tq, Dv), lse[:, :, 0]


_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _flash_bwd_kernel(q_rng, k_rng, q_ref, k_ref, v_ref, seg_q_ref,
                      seg_k_ref, lse_ref, delta_ref, do_ref, dq_ref, dk_ref,
                      dv_ref, dq_sc, dk_sc, dv_sc, *, t: _Tiles):
    """The whole backward pass on the transposed tile sᵀ [block_k, block_q],
    each visible tile visited once. Grid (B*Hkv, n_k, G*n_qw); the last
    axis walks the G query heads of this key/value head, each over the
    query blocks that can see key block kj, and dk/dv accumulate over all of
    them in VMEM scratch. dq of those G heads stays in scratch [G*Tq, D]
    for the whole walk over kj (:func:`_dq_resident_bytes`) and is scaled,
    cast and written once, at the row's last step. P is rebuilt from the
    saved lse (no second softmax)."""
    b, kj, step = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qi = t.first_q(kj) + step % t.n_qw
    block_q = t.block_q
    last = t.G * t.n_qw - 1

    def q_rows(i):  # block i of the resident dq, heads one after the other
        return pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

    @pl.when(step == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    @pl.when(jnp.logical_and(kj == 0, step == 0))
    def _init_dq():
        def zero(i, carry):
            dq_sc[q_rows(i), :] = jnp.zeros((block_q, dq_sc.shape[-1]),
                                            jnp.float32)
            return carry

        jax.lax.fori_loop(0, t.G * t.n_q, zero, 0)

    @pl.when(jnp.logical_and(
        qi < t.n_q, t.visible(qi, kj, q_rng, k_rng, b // t.Hkv)
    ))
    def _compute():
        q = q_ref[0].astype(jnp.float32) * t.scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)

        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32
        )
        mask = _tile_mask(
            t.causal, 1, qi * block_q, kj * t.block_k,
            _lanes(seg_k_ref[0], block_q), seg_q_ref[0], t.window,
            t.rank_bits,
        )
        st = jnp.where(mask, st, _NEG_INF)
        pt = jnp.exp(st - lse_ref[0])
        dv_sc[...] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(
            v, do, _NT, preferred_element_type=jnp.float32
        )
        dst = pt * (dpt - delta_ref[0])
        # q already carries the softmax scale: dK = dSᵀ · (scale · Q).
        dk_sc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
        # dQ = scale · dS · K, the one product over the tile's row axis.
        rows = q_rows((step // t.n_qw) * t.n_q + qi)
        dq_sc[rows, :] += jax.lax.dot_general(
            dst, k, _TN, preferred_element_type=jnp.float32
        )

    @pl.when(step == last)
    def _done():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(kj == t.n_k - 1, step == last))
    def _done_dq():
        def cast(i, carry):
            rows = q_rows(i)
            dq_ref[0, rows, :] = (dq_sc[rows, :] * t.scale).astype(
                dq_ref.dtype
            )
            return carry

        jax.lax.fori_loop(0, t.G * t.n_q, cast, 0)


# The backward kernel's VMEM, of a v5e core's 128 MiB: what it may hold for
# dq from a row's first step to its last, and what it needs beside that (the
# operands' blocks, dk and dv, the float32 tiles of one step: five of
# block_k x block_q).
_DQ_VMEM_BUDGET = 64 * 2 ** 20
_BWD_VMEM_MARGIN = 32 * 2 ** 20


def _dq_resident_bytes(heads: int, Tq: int, D: int, itemsize: int) -> int:
    """VMEM the backward kernel holds for the dq of ``heads`` query heads:
    their float32 accumulator [heads * Tq, D] and the output block it is
    cast into, in the queries' dtype and double-buffered like every output
    block."""
    return heads * Tq * D * (4 + 2 * itemsize)


def _dq_passes(G: int, Tq: int, D: int, itemsize: int) -> int:
    """Over how many rows of the backward kernel's grid the ``G`` query
    heads of a key/value head are spread so that a row's resident dq fits
    :data:`_DQ_VMEM_BUDGET`: 1 wherever a cell runs (one head of 8,192 x
    256: 16 MiB; 4 heads on one of 8,192 x 128: 32 MiB), more for longer
    rows, and a row then makes a partial dk/dv."""
    for passes in range(1, G + 1):
        if G % passes == 0 and _dq_resident_bytes(
                G // passes, Tq, D, itemsize) <= _DQ_VMEM_BUDGET:
            return passes
    raise ValueError(
        f"flash attention's backward holds one query head's dq in fast "
        f"memory: {Tq} x {D} needs "
        f"{_dq_resident_bytes(1, Tq, D, itemsize) / 2 ** 20:.0f} MiB of "
        f"{_DQ_VMEM_BUDGET / 2 ** 20:.0f}; shard the sequence "
        f"(ops.ring_attention) or use backend='blockwise'"
    )


def _flash_backward(q, k, v, seg_q, seg_k, out, lse, g, causal, window,
                    block_q, block_k, interpret, scale, rank_bits=None,
                    dlse=None):
    B, H, Tq, D = q.shape
    Tk, Dv = k.shape[-2], v.shape[-1]
    t = _tiles(q, k, causal, window, block_q, block_k, scale, rank_bits)
    block_q, block_k = t.block_q, t.block_k
    # A grid row walks the query heads whose dq it holds: all G of a
    # key/value head in one pass wherever that fits, else G / passes of them,
    # and the geometry is that of passes times as many key/value heads.
    passes = _dq_passes(t.G, Tq, D, q.dtype.itemsize)
    kv_heads, Hkv = t.Hkv, t.Hkv * passes
    t = t._replace(Hkv=Hkv)
    qr = q.reshape(B * H, Tq, D)
    kr = k.reshape(B * kv_heads, Tk, D)
    vr = v.reshape(B * kv_heads, Tk, Dv)
    gr = g.reshape(B * H, Tq, Dv)
    # delta_i = rowsum(dO * O): the softmax-jacobian correction term.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(B * H, Tq)
    if dlse is not None:
        # d lse_i / d s_ij = p_ij, so the row statistic's cotangent enters
        # the kernel where delta does: ds = p (dp - (delta - dlse)).
        delta = delta - dlse.astype(jnp.float32).reshape(B * H, Tq)
    global_telemetry().registry.counter(
        "attention_backward_traced_total", form="fused"
    ).inc()

    # Transposed tile [block_k, block_q]: per-query statistics are rows, the
    # keys' segment ids the lane-replicated columns. Queries, keys and their
    # gradients are D wide; values, the output's cotangent and the values'
    # gradient Dv.
    def q_row_of(b, step):  # the query head this step walks
        return b * t.G + step // t.n_qw

    def q_spec(width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, kj, step, *_: (
                q_row_of(b, step), t.q_block(kj, step), 0
            ),
        )

    def k_spec(width, rows_a_head=passes):
        return pl.BlockSpec(
            (1, block_k, width),
            lambda b, kj, step, *_: (b // rows_a_head, kj, 0),
        )

    def partial_dtype(x):  # a pass's dk or dv is summed below: not rounded
        return jnp.float32 if passes > 1 else x.dtype

    q_row = pl.BlockSpec(
        (1, 1, block_q),
        lambda b, kj, step, *_: (q_row_of(b, step), 0, t.q_block(kj, step)),
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, t=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, t.n_k, t.G * t.n_qw),
            in_specs=[
                q_spec(D),
                k_spec(D),
                k_spec(Dv),
                pl.BlockSpec(
                    (1, 1, block_q),
                    lambda b, kj, step, *_: (
                        b // Hkv, 0, t.q_block(kj, step)
                    ),
                ),
                pl.BlockSpec(
                    (1, block_k, _LANES),
                    lambda b, kj, step, *_: (b // Hkv, kj, 0),
                ),
                q_row,
                q_row,
                q_spec(Dv),
            ],
            out_specs=[
                # the G query heads of key/value head b, one after the other
                pl.BlockSpec(
                    (1, t.G * Tq, D), lambda b, kj, step, *_: (b, 0, 0)
                ),
                k_spec(D, 1),
                k_spec(Dv, 1),
            ],
            scratch_shapes=[
                pltpu.VMEM((t.G * Tq, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, Dv), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, t.G * Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Tk, D), partial_dtype(k)),
            jax.ShapeDtypeStruct((B * Hkv, Tk, Dv), partial_dtype(v)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_dq_resident_bytes(
                t.G, Tq, D, q.dtype.itemsize) + _BWD_VMEM_MARGIN,
        ),
        interpret=interpret,
    )(_block_ranges(seg_q, t.n_q), _block_ranges(seg_k, t.n_k),
      qr, kr, vr, _row_form(seg_q), _col_form(seg_k),
      _row_form(lse), _row_form(delta), gr)

    def whole(x, like):  # [B * Hkv, Tk, width] from the passes' partials
        x = x.reshape(B, kv_heads, passes, Tk, x.shape[-1])
        return x[:, :, 0] if passes == 1 else x.sum(2).astype(like.dtype)

    return dq.reshape(B, H, Tq, D), whole(dk, k), whole(dv, v)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash_attention(q, k, v, seg_q, seg_k, causal, window, block_q,
                     block_k, interpret, scale, rank_bits):
    out, _lse = _flash_forward(
        q, k, v, seg_q, seg_k, causal, window, block_q, block_k, interpret,
        scale, rank_bits,
    )
    return out


# The two residuals of the backward pass that the forward kernel alone can
# make, by name, and the ``jax.checkpoint`` policy that keeps them and
# nothing else: what it wraps rebuilds ``q``, ``k``, ``v`` and everything
# after the core, and not the core, because the rebuilt forward kernel's
# outputs are then unused and it is dropped. Anywhere else a name is the
# identity. The dense and blockwise backends have no such residuals and no
# names.
_CORE_OUT, _CORE_LSE = "moolib.attn_core.out", "moolib.attn_core.lse"
KEEP_CORES = jax.checkpoint_policies.save_only_these_names(
    _CORE_OUT, _CORE_LSE
)


def _flash_fwd(q, k, v, seg_q, seg_k, causal, window, block_q, block_k,
               interpret, scale, rank_bits):
    out, lse = _flash_forward(
        q, k, v, seg_q, seg_k, causal, window, block_q, block_k, interpret,
        scale, rank_bits,
    )
    out = checkpoint_name(out, _CORE_OUT)
    lse = checkpoint_name(lse, _CORE_LSE)
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, scale,
               rank_bits, res, g, dlse=None):
    q, k, v, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_backward(
        q, k, v, seg_q, seg_k, out, lse, g, causal, window, block_q,
        block_k, interpret, scale, rank_bits, dlse,
    )
    return dq, dk, dv, None, None


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def _merge_form(lse, q):
    """The kernels' row statistic [B * H, Tq] as a caller reads it, [B,
    H, Tq]: a row that saw no key carries the kernels' sentinel, +1e30
    (their backward's ``exp(s - lse)`` is then exactly 0), and reads as
    the empty set's, -1e30."""
    return jnp.where(lse > -_NEG_INF / 2, _NEG_INF, lse).reshape(
        q.shape[:-1])


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11)
)
def _flash_attention_lse(q, k, v, seg_q, seg_k, causal, window, block_q,
                         block_k, interpret, scale, rank_bits):
    """:func:`_flash_attention` with the row statistics as a second,
    differentiable output: the same two kernels, the statistic's
    cotangent taken off ``delta``."""
    out, lse = _flash_forward(
        q, k, v, seg_q, seg_k, causal, window, block_q, block_k, interpret,
        scale, rank_bits,
    )
    return out, _merge_form(lse, q)


def _flash_lse_fwd(*args):
    out, res = _flash_fwd(*args)
    return (out, _merge_form(res[-1], args[0])), res


def _flash_lse_bwd(*args):
    *static, res, (g, dlse) = args
    return _flash_bwd(*static, res, g, dlse)


_flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    segment_ids: Optional[jax.Array] = None,
    kv_segment_ids: Optional[jax.Array] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    rank_bits: Optional[int] = None,
    return_lse: bool = False,
):
    """Pallas flash attention (custom VJP backward), compiled by Mosaic.
    q [B, H, Tq, D], k [B, Hkv, Tk, D], v [B, Hkv, Tk, Dv]: the forward
    and the backward kernel compute at both head sizes as they are given,
    nothing padded.
    ``rank_bits``, ``return_lse``: the module docstring; with ``rank_bits``
    the ids may not decrease along either axis, in group or in rank (the
    kernels skip a tile by its blocks' least and largest id).

    ``interpret=True`` runs the same kernel logic in the Pallas interpreter
    for CPU tests; it is an error on a TPU, where nothing may quietly
    replace the compiled kernel. Without it a non-TPU backend, or a shape
    Mosaic rejects, fails in the caller's compile."""
    if interpret and jax.default_backend() == "tpu":
        raise ValueError("flash_attention(interpret=True) on a TPU backend")
    _check_window(window, causal)
    _check_ranks(rank_bits, causal, segment_ids, kv_segment_ids)
    B, _, Tq, _ = q.shape
    Tk = k.shape[-2]
    seg_q = (
        segment_ids
        if segment_ids is not None
        else jnp.zeros((B, Tq), jnp.int32)
    )
    seg_k = (
        kv_segment_ids
        if kv_segment_ids is not None
        else (
            segment_ids
            if segment_ids is not None
            else jnp.zeros((B, Tk), jnp.int32)
        )
    )
    core = _flash_attention_lse if return_lse else _flash_attention
    return core(
        q, k, v, seg_q, seg_k, causal, window, block_q, block_k, interpret,
        scale, rank_bits,
    )


def merge_attention(out_a, lse_a, out_b, lse_b):
    """One softmax over two key sets from the two sets' own results:
    ``(out_a e^lse_a + out_b e^lse_b) / (e^lse_a + e^lse_b)``, what a
    single call over the union of the keys gives, exactly. ``out`` [..,
    T, Dv], ``lse`` [.., T] as ``return_lse`` gives them (an empty set:
    zeros and -1e30); a row that saw no key in either set is zeros."""
    top = jax.lax.stop_gradient(jnp.maximum(lse_a, lse_b))  # a shift only
    top = jnp.where(top > _NEG_INF / 2, top, 0.0)
    w_a, w_b = jnp.exp(lse_a - top), jnp.exp(lse_b - top)
    total = w_a + w_b
    total = jnp.where(total > 0, total, 1.0)
    out = (
        out_a.astype(jnp.float32) * (w_a / total)[..., None]
        + out_b.astype(jnp.float32) * (w_b / total)[..., None]
    )
    return out.astype(out_a.dtype)


def resolve_backend(Tq: int, Tk: int, block_q: int = 256,
                    block_k: int = 256) -> str:
    """What ``attention(backend="auto")`` runs, decided from what can be
    observed at trace time — the platform and the shape — and nothing else:

    - ``flash`` on a TPU when the effective blocks tile the sequences and
      are multiples of 128, the lane width the kernels' row statistics and
      segment-id blocks are laid out for (``chip_smoke.py`` compiles exactly
      these shapes; a Mosaic rejection of one is a bug and surfaces);
    - ``dense`` when the score matrix is small (≤ 1M entries: an IMPALA
      unroll of T+1 = 21 frames lands here on every platform);
    - ``blockwise`` otherwise.
    """
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if (
        jax.default_backend() == "tpu"
        and Tq % bq == 0
        and Tk % bk == 0
        and bq % _LANES == 0
        and bk % _LANES == 0
    ):
        return "flash"
    if Tq * Tk <= 1024 * 1024:
        return "dense"
    return "blockwise"


_rebuilt = threading.local()  # .keeping: inside keeping_cores(), by thread


@contextlib.contextmanager
def keeping_cores():
    """Entered, while it is traced, by code that a ``jax.checkpoint`` with
    the policy :data:`KEEP_CORES` rebuilds in the backward pass: an
    :func:`attention` call traced inside that runs the flash kernels keeps
    its core's output and row statistics, and counts under
    ``attention_cores_kept_total``."""
    before = getattr(_rebuilt, "keeping", False)
    _rebuilt.keeping = True
    try:
        yield
    finally:
        _rebuilt.keeping = before


def attention(q, k, v, backend: str = "auto", **kw):
    """Dispatcher: 'dense' | 'blockwise' | 'flash' | 'auto'
    (:func:`resolve_backend`). The backend a call runs is on record: the
    process-global counter ``attention_calls_traced_total{backend=}`` counts
    calls where they are traced (once a compile under jit), so whoever
    holds a program to a backend reads what its own trace picked; beside it
    ``attention_cores_kept_total`` counts the flash calls traced inside
    :func:`keeping_cores`, whose forward kernel the backward pass does not
    run again."""
    if backend == "auto":
        backend = resolve_backend(
            q.shape[-2], k.shape[-2], kw.get("block_q", 256),
            kw.get("block_k", 256),
        )
    registry = global_telemetry().registry
    registry.counter("attention_calls_traced_total", backend=backend).inc()
    if backend == "flash" and getattr(_rebuilt, "keeping", False):
        registry.counter("attention_cores_kept_total").inc()
    if backend != "flash":
        kw.pop("block_q", None)  # flash-only knob
        if backend == "dense":
            kw.pop("block_k", None)
    fn = {
        "dense": dense_attention,
        "blockwise": blockwise_attention,
        "flash": flash_attention,
    }.get(backend)
    if fn is None:
        raise ValueError(f"unknown attention backend {backend!r}")
    return fn(q, k, v, **kw)
