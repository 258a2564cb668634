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
from ..ops import ring_attention as ring_ops
from ..parallel.moe import moe_ffn

__all__ = ["TransformerNet", "attend", "moe_aux_losses", "residual_block"]


def segment_ids_from_done(done) -> jax.Array:
    """[T, B] done flags -> [B, T] segment ids (done marks the FIRST frame
    of a new episode, matching the EnvPool convention where a done frame
    already holds the next episode's reset observation)."""
    return jnp.cumsum(done.astype(jnp.int32), axis=0).T


def attend(q, k, v, seg_bt, *, backend: str, ring_axis: str = "sp",
           window: Optional[int] = None, **blocks):
    """The one attention call site of the models: causal, cut at segment
    boundaries, ``[B, H, T, D]`` in and out (``k``/``v`` may carry fewer
    heads). ``ring`` / ``zigzag`` run across the ``ring_axis`` mesh axis
    inside shard_map; everything else is :func:`attn_ops.attention` and
    what its ``backend`` resolves to. ``blocks``: ``block_q``/``block_k``
    where the caller sets them."""
    if backend in ("ring", "zigzag"):
        if window is not None or k.shape[1] != q.shape[1]:
            raise ValueError(
                f"the {backend} backend has neither a window nor grouped "
                "heads"
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
    return attn_ops.attention(
        q, k, v, backend=backend, causal=True, segment_ids=seg_bt,
        window=window, **blocks,
    )


def residual_block(x, norm1, mixer, norm2, mlp):
    """The pre-norm residual skeleton every block shares:
    ``h = x + mixer(norm1(x)); out = h + mlp(norm2(h))``."""
    x = x + mixer(norm1(x))
    return x + mlp(norm2(x))


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


class _MoEMlp(nn.Module):
    """Switch/GShard MoE MLP for a transformer block.

    Routing/capacity/losses come from :func:`moolib_tpu.parallel.moe.moe_ffn`;
    per-call aux (load-balance loss, router z-loss, drop fraction) is sown
    into the ``intermediates`` collection — train with
    ``apply(..., mutable=["intermediates"])`` and fold
    :func:`moe_aux_losses` into the loss so capacity drops are neither
    silent nor unpenalized. The router param is deliberately NOT named
    ``kernel`` so tensor-parallel shape derivation (parallel/tp.py) never
    mistakes it for a projection.
    """

    num_experts: int
    mlp_ratio: int
    top_k: int
    capacity_factor: float

    @nn.compact
    def __call__(self, x):  # [T, B, E] -> [T, B, E]
        T, B, E = x.shape
        d_hidden = self.mlp_ratio * E
        init = nn.initializers.lecun_normal()
        # batch_axis=0: the expert axis is a batch of independent matrices,
        # not receptive field — without it fan_in becomes E_experts * d_in
        # and every expert starts sqrt(num_experts)x too small (the
        # per-expert scaling moe_params uses).
        expert_init = nn.initializers.lecun_normal(batch_axis=(0,))
        params = {
            "router": self.param("router", init, (E, self.num_experts)),
            "w_up": self.param(
                "w_up", expert_init, (self.num_experts, E, d_hidden)
            ),
            "w_down": self.param(
                "w_down", expert_init, (self.num_experts, d_hidden, E)
            ),
        }
        y, aux = moe_ffn(
            params, x.reshape(T * B, E),
            top_k=self.top_k, capacity_factor=self.capacity_factor,
        )
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(T, B, E)


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


def moe_aux_losses(intermediates) -> dict:
    """Aggregate every MoE layer's sown aux from a flax ``intermediates``
    collection: summed load-balance and router-z losses (add them to the
    training loss, typically with weights ~1e-2 / ~1e-3) and the mean drop
    fraction (log it — silent drops are a capacity bug)."""
    found = sown_dicts(intermediates, "load_balance_loss")
    if not found:
        raise ValueError("no MoE aux entries in intermediates — was the "
                         "model built with mlp='moe' and applied with "
                         "mutable=['intermediates']?")
    n = len(found)
    return {
        "load_balance_loss": sum(a["load_balance_loss"] for a in found),
        "router_z_loss": sum(a["router_z_loss"] for a in found),
        "drop_fraction": sum(a["drop_fraction"] for a in found) / n,
        "n_moe_layers": n,
    }


class _Block(nn.Module):
    num_heads: int
    mlp_ratio: int
    backend: str
    ring_axis: str
    mlp: str = "dense"
    num_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        if self.mlp not in ("dense", "moe"):
            raise ValueError(
                f"unknown mlp type {self.mlp!r}; expected 'dense' or 'moe'"
            )
        attention = _SelfAttention(
            self.num_heads, self.backend, self.ring_axis, name="attn"
        )
        if self.mlp == "moe":
            mlp = _MoEMlp(
                self.num_experts, self.mlp_ratio, self.moe_top_k,
                self.moe_capacity_factor, name="moe",
            )
        else:
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
    mlp: str = "dense"  # dense | moe (Switch/GShard blocks; see _MoEMlp)
    num_experts: int = 8
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

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
                self.ring_axis, mlp=self.mlp,
                num_experts=self.num_experts, moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                name=f"block_{i}",
            )(x, segment_ids, positions)

        x = nn.LayerNorm()(x.astype(jnp.float32))
        policy_logits = nn.Dense(self.num_actions, name="policy")(x)
        baseline = nn.Dense(1, name="baseline")(x).squeeze(-1)
        return (policy_logits, baseline), core_state

    def initial_state(self, batch_size: int) -> Tuple:
        return ()
