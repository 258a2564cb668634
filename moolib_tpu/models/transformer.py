"""Transformer agent: long-context policy/value model.

The reference's model zoo stops at MLP/LSTM/ResNet (reference:
examples/atari/models.py, examples/a2c.py:47-83) — this adds the
long-context family, built on the attention stack of
:mod:`moolib_tpu.ops.attention` / :mod:`moolib_tpu.ops.ring_attention`.

Same agent calling convention as every other model
(:mod:`moolib_tpu.models.core`):

    (logits_TBA, baseline_TB), state = net.apply(params, obs, done, state)

Design:
- The unroll IS the context: attention is causal over the T axis and
  additionally **segment-masked** so no query attends across an episode
  reset (segment ids = running count of ``done`` per batch lane). State
  between unrolls is not carried (``core_state = ()``), mirroring how
  context-window models consume RL unrolls; history length is set by
  ``unroll_length``.
- Pre-LN blocks, learned positional embedding over unroll positions, GELU
  MLP; attention backend selectable: ``dense`` (short T), ``blockwise``
  (O(T) memory), ``flash`` (pallas TPU kernel), ``ring`` (sequence-parallel
  across the ``sp`` mesh axis — call inside shard_map with the T axis
  sharded and pass globally-correct ``segment_ids``/``positions``), or
  ``zigzag`` (the load-balanced causal layout: apply
  :func:`moolib_tpu.ops.ring_attention.zigzag_order` to the T axis of
  obs/done/segment_ids/positions before shard_map; every device then does
  equal causal work).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops import attention as attn_ops
from ..ops import hyper_mix
from ..ops import ring_attention as ring_ops

__all__ = [
    "TransformerNet",
    "attend",
    "hyper_coefficients",
    "hyper_read",
    "hyper_residual_block",
    "hyper_write",
    "residual_block",
]


def segment_ids_from_done(done) -> jax.Array:
    """[T, B] done flags -> [B, T] segment ids (done marks the FIRST frame
    of a new episode, matching the EnvPool convention where a done frame
    already holds the next episode's reset observation)."""
    return jnp.cumsum(done.astype(jnp.int32), axis=0).T


def attend(q, k, v, seg_bt, *, backend: str, ring_axis: str = "sp",
           window: Optional[int] = None, scale: Optional[float] = None,
           kv_seg_bt=None, causal: bool = True, rank_bits=None,
           return_lse: bool = False, **blocks):
    """The one attention call site of the models: causal, cut at segment
    boundaries, ``[B, H, T, D]`` in and ``[B, H, T, Dv]`` out (``k``/``v``
    may carry fewer heads, ``v`` another head size). ``ring`` / ``zigzag``
    run across the ``ring_axis`` mesh axis inside shard_map; everything
    else is :func:`attn_ops.attention` and what its ``backend`` resolves
    to. ``scale``: of the scores, where it is not ``D ** -0.5``.
    ``kv_seg_bt`` / ``causal=False``: a second key set with ids of its own
    and no positions (chunk summaries); ``rank_bits``: how many low bits of
    both sets' ids are a rank (a key is seen where the high bits, the
    group, equal the query's and its rank is strictly lower, as
    :mod:`attn_ops` has it); ``return_lse``: ``(o, lse)`` and
    not ``o``, the row statistics differentiable. ``blocks``: ``block_q``
    / ``block_k`` where the caller sets them."""
    if backend in ("ring", "zigzag"):
        if (window is not None or scale is not None or not causal
                or kv_seg_bt is not None or rank_bits is not None
                or return_lse
                or k.shape[1] != q.shape[1] or v.shape[-1] != q.shape[-1]):
            raise ValueError(
                f"the {backend} backend has no window, no grouped heads, "
                "one head size, one score scale, one causal key set and "
                "no row statistics"
            )
        if backend == "ring":
            return ring_ops.ring_attention(
                q, k, v, axis_name=ring_axis, causal=True,
                segment_ids=seg_bt, kv_segment_ids=seg_bt,
            )
        # Caller feeds zigzag-laid-out shards (zigzag_order applied to the
        # T axis of obs/done/segment_ids/positions before shard_map) —
        # causal work then balances across the sp axis.
        return ring_ops.zigzag_ring_attention(
            q, k, v, axis_name=ring_axis, segment_ids=seg_bt,
            kv_segment_ids=seg_bt,
        )
    if scale is not None:
        blocks["scale"] = scale
    if kv_seg_bt is not None:
        blocks["kv_segment_ids"] = kv_seg_bt
    if rank_bits is not None:
        blocks["rank_bits"] = rank_bits
    if return_lse:
        blocks["return_lse"] = True
    return attn_ops.attention(
        q, k, v, backend=backend, causal=causal, segment_ids=seg_bt,
        window=window, **blocks,
    )


def residual_block(x, norm1, mixer, norm2, mlp, merge1=jnp.add,
                   merge2=jnp.add):
    """The pre-norm residual skeleton of a block with one stream:
    ``h = x + mixer(norm1(x)); out = h + mlp(norm2(h))``. ``merge1`` /
    ``merge2``: what joins the stream and a sublayer's output where it is
    not their sum (``(x, y) -> x'``: a skeleton that scales and shifts
    both)."""
    x = merge1(x, mixer(norm1(x)))
    return merge2(x, mlp(norm2(x)))


def hyper_coefficients(streams, phi, b, alpha, *, norm_eps: float,
                       sinkhorn_iters: int, eps: float, res_clamp):
    """The mixing coefficients of one sublayer on the residual skeleton
    with several streams (hyper-connections, arXiv:2409.19606, the residual
    matrix made doubly stochastic by Sinkhorn-Knopp, arXiv:2512.24880).
    All of it a function of the token's own streams, in float32:

        xt    = vec(X) / sqrt(mean(vec(X)^2) + norm_eps)
        m     = xt phi                          phi [n C, n^2 + 2n]
        pre   = sigmoid(a_pre m[:n] + b[:n])
        post  = 2 sigmoid(a_post m[n:2n] + b[n:2n])
        M     = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), *res_clamp))
        sinkhorn_iters times: M /= rowsum(M) + eps; M /= colsum(M) + eps

    ``streams`` [n, N, C] (N tokens); ``alpha = (a_pre, a_post, a_res)``.
    Returns ``pre`` [n, N], ``post`` [n, N], ``res`` [n, n, N] (``res[i,
    j]`` weighs stream j in new stream i) with the tokens on the minor
    axis, where a 4 x 4 matrix a token costs no padding, and the counters
    of the mixing: the largest ``|sum - 1|`` over ``res``'s rows and over
    its columns, and how many entries stood at the clip. The gradient goes
    through every iteration and through the clip (zero outside it)."""
    with jax.named_scope("moolib.lm.hc_mix"):
        n, N, C = streams.shape
        x32 = streams.astype(jnp.float32)
        inv_rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=(0, 2)) + norm_eps)
        # vec(X) is stream-major, so phi's rows are n blocks of C: the
        # product is taken before the division (one number a token), in
        # float32 proper.
        m = jnp.einsum(
            "inc,ick->kn", x32, phi.astype(jnp.float32).reshape(n, C, -1),
            precision=jax.lax.Precision.HIGHEST,
        ) * inv_rms
        a = alpha.astype(jnp.float32)
        bias = b.astype(jnp.float32)[:, None]  # beside m [n^2 + 2n, N]
        pre = jax.nn.sigmoid(a[0] * m[:n] + bias[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + bias[n:2 * n])
        raw = (a[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, N)
        lo, hi = res_clamp
        res = jnp.exp(jnp.clip(raw, lo, hi))
        for _ in range(sinkhorn_iters):
            res = res / (jnp.sum(res, axis=1, keepdims=True) + eps)
            res = res / (jnp.sum(res, axis=0, keepdims=True) + eps)
        made, logits = jax.lax.stop_gradient((res, raw))
        counters = {
            "hc_row_sum_gap": jnp.max(jnp.abs(made.sum(axis=1) - 1.0)),
            "hc_col_sum_gap": jnp.max(jnp.abs(made.sum(axis=0) - 1.0)),
            "hc_res_clamped": jnp.sum(
                jnp.logical_or(logits <= lo, logits >= hi)
            ).astype(jnp.float32),
        }
        return pre, post, res, counters


def hyper_read(streams, pre):
    """``h = sum_i pre[i] X[i]`` in float32: ``streams`` [n, N, C],
    ``pre`` [n, N]."""
    with jax.named_scope("moolib.lm.hc_pre"):
        x32 = streams.astype(jnp.float32)
        return sum(pre[i][:, None] * x32[i] for i in range(len(pre)))


def hyper_write(streams, coef, y):
    """``X'[i] = sum_j res[i, j] X[j] + post[i] y`` summed in float32 and
    stored in the streams' dtype: ``streams`` [n, N, C], ``coef`` [n^2 +
    2n, N] (``pre``, ``post``, the rows of ``res``), ``y`` [N, C]. The
    fused pass of :mod:`moolib_tpu.ops.hyper_mix` where
    ``hyper_mix.mix_path`` says so, these lines otherwise."""
    if hyper_mix.mix_path(streams.shape, streams.dtype) == "fused":
        return hyper_mix.write(streams, coef, y)
    with jax.named_scope("moolib.lm.hc_post"):
        n = streams.shape[0]
        post, res = coef[n:2 * n], coef[2 * n:].reshape(n, n, -1)
        x32 = streams.astype(jnp.float32)
        y32 = y.astype(jnp.float32)
        return jnp.stack([
            sum(res[i, j][:, None] * x32[j] for j in range(n))
            + post[i][:, None] * y32
            for i in range(n)
        ]).astype(streams.dtype)


def hyper_residual_block(streams, mix1, norm1, mixer, mix2, norm2, mlp):
    """The residual skeleton of a block with ``n`` streams, ``streams``
    [n, T, B, C]: each of the two sublayers reads a learned mixture of the
    streams and writes back through a learned gate, and the streams are
    remixed by a doubly stochastic matrix,

        h = sum_i pre[i] X[i];  y = F(norm(h))
        X'[i] = sum_j res[i, j] X[j] + post[i] y

    ``mix1`` / ``mix2``: ``streams [n, N, C] -> (h, streams, coef)``, the
    sublayer's read side: its input ``h`` [N, C] in float32, the streams
    for its write side to take, and its coefficients ``[n^2 + 2n, N]``
    (``pre``, ``post``, the rows of ``res``: :func:`hyper_coefficients`).
    The weighted sums are taken in float32 and the streams stored in their
    own dtype; where the shapes tile on a TPU both sides are the fused
    passes of :mod:`moolib_tpu.ops.hyper_mix` (``hyper_mix.mix_path``)."""
    n, T, B, C = streams.shape
    flat = streams.reshape(n, T * B, C)
    for mix, norm, f in ((mix1, norm1, mixer), (mix2, norm2, mlp)):
        h, flat, coef = mix(flat)
        y = f(norm(h.reshape(T, B, C)))
        flat = hyper_write(flat, coef, y.reshape(T * B, C))
    return flat.reshape(n, T, B, C)


class _SelfAttention(nn.Module):
    num_heads: int
    backend: str
    ring_axis: str

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        # x: [T, B, E] -> attention in [B, H, T, D].
        T, B, E = x.shape
        assert E % self.num_heads == 0, (E, self.num_heads)
        D = E // self.num_heads
        qkv = nn.Dense(3 * E, use_bias=False, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):  # [T, B, E] -> [B, H, T, D]
            return t.reshape(T, B, self.num_heads, D).transpose(1, 2, 0, 3)

        o = attend(
            heads(q), heads(k), heads(v), seg_bt, backend=self.backend,
            ring_axis=self.ring_axis,
        )
        o = o.transpose(2, 0, 1, 3).reshape(T, B, E)
        return nn.Dense(E, use_bias=False, name="out")(o)


def sown_dicts(intermediates, marker: str) -> list:
    """Every dict with the key ``marker`` that a module sowed into a flax
    ``intermediates`` collection, in traversal order."""
    found = []

    def walk(node):
        if isinstance(node, dict):
            if marker in node:
                found.append(node)
            else:
                for v in node.values():
                    walk(v)
        elif isinstance(node, (tuple, list)):
            for v in node:
                walk(v)

    walk(intermediates)
    return found


class _Block(nn.Module):
    num_heads: int
    mlp_ratio: int
    backend: str
    ring_axis: str

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        attention = _SelfAttention(
            self.num_heads, self.backend, self.ring_axis, name="attn"
        )
        width = x.shape[-1]

        def mlp(h):
            h = nn.gelu(nn.Dense(self.mlp_ratio * width)(h))
            return nn.Dense(width)(h)

        return residual_block(
            x, nn.LayerNorm(), lambda h: attention(h, seg_bt, positions),
            nn.LayerNorm(), mlp,
        )


class TransformerNet(nn.Module):
    """Causal segment-masked transformer over the unroll axis."""

    num_actions: int
    d_model: int = 128
    num_layers: int = 2
    num_heads: int = 4
    mlp_ratio: int = 4
    max_len: int = 2048
    attention_backend: str = "auto"  # dense|blockwise|flash|ring|zigzag|auto
    ring_axis: str = "sp"
    compute_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, obs, done, core_state, segment_ids=None,
                 positions=None):
        # obs: [T, B, F] float vectors or [T, B, H, W, C] uint8 pixels.
        T, B = obs.shape[:2]
        x = obs.astype(self.compute_dtype)
        if x.ndim == 5:  # pixels: small conv torso, stride-8 downsample
            x = x.reshape(T * B, *obs.shape[2:]) / 255.0
            x = nn.Conv(32, (8, 8), strides=(4, 4))(x)
            x = nn.relu(x)
            x = nn.Conv(self.d_model, (4, 4), strides=(2, 2))(x)
            x = nn.relu(x)
            x = x.mean(axis=(1, 2))  # global average pool
            x = x.reshape(T, B, self.d_model)
        else:
            x = nn.Dense(self.d_model)(x)

        if positions is None:
            if self.attention_backend in ("ring", "zigzag"):
                # A local arange would silently embed wrong positions on
                # every shard past the first — same failure class as the
                # segment_ids check below, so same loud error.
                raise ValueError(
                    f"{self.attention_backend} backend needs globally-"
                    "correct positions for each local shard (zigzag: in "
                    "zigzag_order layout)"
                )
            positions = jnp.arange(T)
        pos_emb = nn.Embed(self.max_len, self.d_model, name="pos_emb")(
            positions
        )
        x = x + pos_emb[:, None, :].astype(self.compute_dtype)

        if segment_ids is None:
            if self.attention_backend in ("ring", "zigzag"):
                raise ValueError(
                    f"{self.attention_backend} backend needs "
                    "globally-correct segment_ids; compute them from the "
                    "full done sequence before shard_map and pass the "
                    "local shard in (zigzag: in zigzag_order layout)"
                )
            segment_ids = segment_ids_from_done(done)

        for i in range(self.num_layers):
            x = _Block(
                self.num_heads, self.mlp_ratio, self.attention_backend,
                self.ring_axis, name=f"block_{i}",
            )(x, segment_ids, positions)

        x = nn.LayerNorm()(x.astype(jnp.float32))
        policy_logits = nn.Dense(self.num_actions, name="policy")(x)
        baseline = nn.Dense(1, name="baseline")(x).squeeze(-1)
        return (policy_logits, baseline), core_state

    def initial_state(self, batch_size: int) -> Tuple:
        return ()
