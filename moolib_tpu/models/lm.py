"""Decoder language model as a token-level actor-critic agent.

Observation = token id, action = next token: the policy logits are the
language-model head, the baseline one value unit on the final hidden state.
Same agent calling convention as every other model
(:mod:`moolib_tpu.models.core`):

    (logits_TBA, baseline_TB), state = net.apply(params, obs, done, state)

with ``obs`` [T, B] integer token ids and ``initial_state`` ``()``: the
unroll is the context, causal over T and cut at episode boundaries
(``segment_ids_from_done``), as in :class:`TransformerNet`; positions run
0..T-1 over the unroll and are not reset at a boundary.

The stack is **described by data**: ``layers`` is a list, one entry a
block, each naming its attention kind and its MLP kind, and
``attention_kinds`` says what a kind is (its window, its rotary
parameters). A model with three windowed layers to one full layer is a
list, not a constructor flag. Blocks are pre-norm (RMS), rotary positions
(plain, or YaRN-scaled) over the whole head, grouped key/value heads,
sparse MLPs through :func:`moolib_tpu.parallel.moe.moe_dropless`. They use
the residual skeleton and the one attention call site of
:mod:`moolib_tpu.models.transformer`.

**A share of a layer.** ``num_heads`` / ``num_kv_heads``, ``experts_held``
and ``vocab_size`` are what *this chip* holds of a layer that several chips
divide: its query heads and their key/value head, ``(first, count)`` of the
``num_experts`` the router scores, its rows of the embedding and of the
untied head. Every width stays the model's. The attention output and the
expert output are then the partial sums a tensor-parallel group would
all-reduce; here they go on as they are, and nothing stands in for the
absent chips.

Counters of the expert layers (assignments held and total, tokens no held
expert served, the fullest expert's load, layers that ran over the
worst-case buffer) are sown into ``intermediates``; :func:`learn_apply`
gives the learner the three-element ``apply_fn`` that sums them over
layers into the step's metrics.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..parallel.moe import moe_dropless
from .transformer import (attend, residual_block, segment_ids_from_done,
                          sown_dicts)

__all__ = [
    "AttentionKind",
    "DecoderLM",
    "Rope",
    "decoder_lm",
    "learn_apply",
    "rope_inv_freq",
    "router_loads",
]


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary parameters of one attention kind (the keys of a
    ``rope_parameters`` entry). ``factor`` None is the plain rotary
    embedding; a number the YaRN blend."""

    theta: float = 10000.0
    factor: Optional[float] = None
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    truncate: bool = True


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    window: Optional[int]  # None: full causal attention
    rope: Rope


def rope_inv_freq(rope: Rope, head_dim: int) -> np.ndarray:
    """``inv_freq`` [head_dim / 2], float64. Plain: ``theta^(-2i/d)``.
    YaRN (Peng et al. 2023, as ``transformers`` computes it): frequencies
    that turn more than ``beta_fast`` times over the original context keep
    their value, those that turn less than ``beta_slow`` times are divided
    by ``factor``, and a linear ramp over the index blends between."""
    i = np.arange(0, head_dim, 2, dtype=np.float64)
    inv = rope.theta ** (-i / head_dim)
    if rope.factor is None:
        return inv

    def dim_of(turns):  # the index whose frequency makes `turns` turns
        return (
            head_dim
            * math.log(rope.original_max_position_embeddings
                       / (turns * 2 * math.pi))
            / (2 * math.log(rope.theta))
        )

    low, high = dim_of(rope.beta_fast), dim_of(rope.beta_slow)
    if rope.truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return inv / rope.factor * ramp + inv * (1 - ramp)


def _rotary(x, cos, sin):
    """x [T, B, H, D]; cos/sin [T, D], float32. The half-split form:
    ``x * cos + rotate_half(x) * sin``."""
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = x32 * cos[:, None, None] + rotated * sin[:, None, None]
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (x32 * scale).astype(self.dtype)


class _Attention(nn.Module):
    kind: AttentionKind
    num_heads: int
    num_kv_heads: int
    head_dim: int
    backend: str
    block: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        T, B, _ = x.shape
        H, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(name, heads):
            return nn.Dense(
                heads * D, use_bias=False, dtype=self.dtype, name=name
            )(x).reshape(T, B, heads, D)

        with jax.named_scope("moolib.lm.attn_proj"):
            rope = self.kind.rope
            angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
                rope_inv_freq(rope, D), jnp.float32
            )
            angle = jnp.concatenate([angle, angle], axis=-1)  # [T, D]
            cos = jnp.cos(angle) * rope.attention_factor
            sin = jnp.sin(angle) * rope.attention_factor
            q = _rotary(proj("q", H), cos, sin)
            k = _rotary(proj("k", Hkv), cos, sin)
            v = proj("v", Hkv)
            # [T, B, heads, D] -> [B, heads, T, D]
            q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        with jax.named_scope("moolib.lm.attn_core"):
            o = attend(
                q, k, v, seg_bt, backend=self.backend,
                window=self.kind.window, block_q=self.block,
                block_k=self.block,
            )
        with jax.named_scope("moolib.lm.attn_proj"):
            o = o.transpose(2, 0, 1, 3).reshape(T, B, H * D)
            return nn.Dense(
                x.shape[-1], use_bias=False, dtype=self.dtype, name="o"
            )(o)


class _SparseMlp(nn.Module):
    """Gated experts behind a router over ``num_experts``; ``held`` of
    them live here (see :func:`moe_dropless`)."""

    num_experts: int
    held: Tuple[int, int]
    top_k: int
    d_ff: int
    buffer_rows: Optional[int]

    @nn.compact
    def __call__(self, x):  # [T, B, d] -> [T, B, d]
        T, B, d = x.shape
        count = self.held[1]
        init = nn.initializers.lecun_normal()
        # batch_axis=0: the expert axis is a batch of matrices, not fan-in.
        expert_init = nn.initializers.lecun_normal(batch_axis=(0,))
        params = {
            "router": self.param("router", init, (d, self.num_experts)),
            "w_gate": self.param("w_gate", expert_init, (count, d, self.d_ff)),
            "w_up": self.param("w_up", expert_init, (count, d, self.d_ff)),
            "w_down": self.param("w_down", expert_init, (count, self.d_ff, d)),
        }
        y, aux = moe_dropless(
            params, x.reshape(T * B, d), top_k=self.top_k, held=self.held,
            buffer_rows=self.buffer_rows,
        )
        self.sow("intermediates", "moe_router_load", aux.pop("moe_router_load"))
        self.sow("intermediates", "moe_counters", aux)
        return y.reshape(T, B, d)


@dataclasses.dataclass(frozen=True)
class _Sizes:
    """What a block needs of the model's description."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    held: Tuple[int, int]
    top_k: int
    moe_intermediate_size: int
    moe_buffer_rows: Optional[int]
    rms_norm_eps: float
    compute_dtype: jnp.dtype
    attention_backend: str
    attention_block: int


class _Block(nn.Module):
    kind: AttentionKind
    mlp: str
    net: _Sizes

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        net = self.net
        if self.mlp != "sparse":
            raise ValueError(f"unknown mlp kind {self.mlp!r}; have 'sparse'")

        def norm(name):
            return RMSNorm(net.rms_norm_eps, net.compute_dtype, name=name)

        attention = _Attention(
            self.kind, net.num_heads, net.num_kv_heads, net.head_dim,
            net.attention_backend, net.attention_block, net.compute_dtype,
            name="attn",
        )
        mlp = _SparseMlp(
            net.num_experts, net.held, net.top_k, net.moe_intermediate_size,
            net.moe_buffer_rows, name="moe",
        )
        return residual_block(
            x, norm("norm1"), lambda h: attention(h, seg_bt, positions),
            norm("norm2"), mlp,
        )


class DecoderLM(nn.Module):
    """Causal, segment-masked decoder over token ids; see the module
    docstring. Build it from a configuration's JSON with
    :func:`decoder_lm`."""

    vocab_size: int  # rows of the embedding and of the head held here
    hidden_size: int
    layers: Tuple[Tuple[str, str], ...]  # (attention kind, mlp kind) a block
    attention_kinds: Tuple[Tuple[str, AttentionKind], ...]
    num_heads: int  # query heads held here
    num_kv_heads: int
    head_dim: int
    num_experts: int  # the router's width
    top_k: int
    moe_intermediate_size: int
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); all
    # The expert layers' buffer: None is the worst case, T * top_k rows; a
    # number is the size that usually does, with the worst case behind it
    # and the rows rebuilt in the backward pass (moe_dropless).
    moe_buffer_rows: Optional[int] = None
    rms_norm_eps: float = 1e-6
    compute_dtype: jnp.dtype = jnp.float32
    attention_backend: str = "auto"
    attention_block: int = 256

    def _sizes(self) -> _Sizes:
        return _Sizes(
            self.num_heads, self.num_kv_heads, self.head_dim,
            self.num_experts, self.experts_held or (0, self.num_experts),
            self.top_k, self.moe_intermediate_size, self.moe_buffer_rows,
            self.rms_norm_eps, jnp.dtype(self.compute_dtype),
            self.attention_backend, self.attention_block,
        )

    @nn.compact
    def __call__(self, obs, done, core_state):
        T = obs.shape[0]
        x = nn.Embed(
            self.vocab_size, self.hidden_size, dtype=self.compute_dtype,
            name="embed",
        )(obs.astype(jnp.int32))
        seg_bt = segment_ids_from_done(done)
        positions = jnp.arange(T)
        kinds, sizes = dict(self.attention_kinds), self._sizes()
        for i, (attention, mlp) in enumerate(self.layers):
            x = _Block(kinds[attention], mlp, sizes, name=f"block_{i}")(
                x, seg_bt, positions
            )
        with jax.named_scope("moolib.lm.head"):
            x = RMSNorm(
                self.rms_norm_eps, self.compute_dtype, name="final_norm"
            )(x)
            logits = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.compute_dtype,
                name="head",
            )(x).astype(jnp.float32)
            baseline = nn.Dense(1, name="baseline")(
                x.astype(jnp.float32)
            ).squeeze(-1)
        return (logits, baseline), core_state

    def initial_state(self, batch_size: int) -> Tuple:
        return ()


def decoder_lm(*, layers, attention_kinds, experts_held=None,
               **kwargs) -> DecoderLM:
    """A :class:`DecoderLM` from JSON-shaped arguments: ``layers`` a list
    of ``{"attention": kind, "mlp": "sparse"}``, ``attention_kinds`` a
    mapping ``kind -> {"window": int or null, "rope": {...}}`` whose
    ``rope`` holds the fields of :class:`Rope`."""
    kinds = tuple(
        (name, AttentionKind(spec.get("window"), Rope(**spec["rope"])))
        for name, spec in sorted(attention_kinds.items())
    )
    return DecoderLM(
        layers=tuple((l["attention"], l["mlp"]) for l in layers),
        attention_kinds=kinds,
        experts_held=None if experts_held is None else tuple(experts_held),
        **kwargs,
    )


def _sum_counters(intermediates) -> dict:
    """Every expert layer's sown counters, summed over layers; the two
    loads (the fullest held expert's and the mean) averaged over them."""
    layers = sown_dicts(intermediates, "moe_assignments_total")
    total: dict = {}
    for layer in layers:
        for name, value in layer.items():
            total[name] = total.get(name, 0.0) + value
    for name in ("moe_load_max", "moe_load_mean"):
        if name in total:
            total[name] = total[name] / len(layers)
    return total


def learn_apply(net: DecoderLM) -> Callable:
    """The learner's ``apply_fn`` for ``net``, in the three-element
    convention of :func:`moolib_tpu.learner.impala_loss`: the third is the
    expert layers' counters summed over layers, which the loss passes
    through to the step's metrics (no loss term: the model's configuration
    declares no routing loss)."""

    def apply(params, obs, done, core_state):
        (out, state), inter = net.apply(
            params, obs, done, core_state, mutable=["intermediates"]
        )
        return out, state, _sum_counters(inter)

    return apply


def router_loads(net: DecoderLM) -> Callable:
    """``(params, obs, done) -> [layers, num_experts] int32``: the
    assignments every expert layer's router sends to each of its experts,
    held here or not, in the order of ``net.layers``. A forward pass and
    nothing else: for whoever has to know the routing of given weights on
    given tokens (the benchmark's seeding reads it)."""

    def loads(params, obs, done):
        _, inter = net.apply(
            params, obs, done, net.initial_state(obs.shape[1]),
            mutable=["intermediates"],
        )
        blocks = inter["intermediates"]
        return jnp.stack([
            blocks[f"block_{i}"]["moe"]["moe_router_load"][0]
            for i in range(len(net.layers))
        ])

    return loads
