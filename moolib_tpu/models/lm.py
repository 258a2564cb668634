"""Decoder language model as a token-level actor-critic agent.

Observation = token id, action = next token: the policy logits are the
language-model head, the baseline one value unit on the final hidden state.
Same agent calling convention as every other model
(:mod:`moolib_tpu.models.core`):

    (logits_TBA, baseline_TB), state = net.apply(params, obs, done, state)

with ``obs`` [T, B] integer token ids. For a softmax block the unroll is
the context, causal over T and cut at episode boundaries
(``segment_ids_from_done``), as in :class:`TransformerNet`; positions run
0..T-1 over the unroll and are not reset at a boundary; such a block
carries nothing from call to call, and a stack of them has
``initial_state`` ``()``. A delta-rule block (below) carries a state, and
``initial_state`` is then no longer ``()``: a flat tuple of float32
leaves ``[B, ...]``, two a stateful entry of ``layers``, which the call
returns as it stands after its last position (the learner hands it in
with the batch, ``make_act_step`` to the next call, as the LSTM's).

The stack is **described by data**: ``layers`` is a list, one entry a
block (or, with ``repeat``, that many identical blocks run as one scan
over their stacked parameters: one block's code, one block's compile),
each naming its attention kind and its MLP kind, and ``attention_kinds``
says what a kind is (its window, its rotary parameters, and for latent
attention its ranks and the split of its head). A model with three
windowed layers to one full layer is a list, not a constructor flag.
Blocks are pre-norm (RMS) on a residual skeleton and the one attention
call site of :mod:`moolib_tpu.models.transformer`. ``residual`` says which
skeleton: absent, one stream and ``x + F(norm(x))``; ``{"streams": n,
...}`` (:class:`Residual`), ``n`` streams that every sublayer reads through a
learned mixture, writes through a learned gate and remixes by a matrix that
Sinkhorn iterations make doubly stochastic
(:func:`~moolib_tpu.models.transformer.hyper_residual_block`). The
embedding is then replicated into the streams, the blocks, their scan and
their rebuild carry ``[n, T, B, d]``, and the streams are summed before
the final norm. ``"scaled"``, the third: one stream whose every sublayer
``s`` has four learned vectors of the hidden width and joins the stream
and its own output by

    x <- a_r,s (x + b_r,s) + a_y,s (F_s(norm_s(x)) + b_y,s)

``a`` starting at 1 and ``b`` at 0, where it is the first skeleton.

Attention kinds. Without ``latent``: rotary positions (plain, or
YaRN-scaled) over the whole head, grouped key/value heads, three dense
projections. With ``latent`` (multi-head latent attention, DeepSeek-V2):

    c_q = rms(x W_qa);  q = c_q W_qb        -> heads of [nope | rope]
    [c_kv | k_r] = x W_kva;  c_kv = rms(c_kv)
    [k_nope | v] a head = c_kv W_kvb;  k = [k_nope | rotary(k_r)]

one rotary key a position shared by every head, rotary over the ``rope``
part only (plain or YaRN-scaled, as the kind's ``rope`` says), scores
scaled by ``(nope + rope)^-1/2`` or by the kind's ``softmax_scale`` (YaRN's
``mscale^2`` goes there). The value head ``v`` may be narrower than the
query/key head ``nope + rope``: every attention backend takes the two
sizes apart. This is the decompressed form a training forward computes;
the absorbed form with one key head belongs to a cache, which the repo has
none of.

With ``eva`` (chunk-summary attention, EVA as EvaByte ships it; dense
projections and the rotary as the first kind has them) the kind's
``window`` W is a *block* of positions and ``chunk_size`` c the length of
a chunk; ``phi`` and ``mu`` are ``[D]`` a key/value head, learned:

    chunk j  = positions [c j, c j + c - 1];  P_j = those of them in the
               episode of the chunk's last position
    a_{j,s}  = softmax over s in P_j of (k_s . phi) D^-1/2
    kt_j     = sum_s a_{j,s} k_s + mu;      vt_j = sum_s a_{j,s} v_s
    L_t      = {s: floor(s/W) = floor(t/W), s <= t, episode(s) = episode(t)}
    R_t      = {j: floor(c j / W) < floor(t/W),
                   episode(c j + c - 1) = episode(t)}
    o_t      = ONE softmax over the scores q_t . k_s (s in L_t) and
               q_t . kt_j (j in R_t), applied to the v_s and the vt_j

a query reads its own window exactly and causally, and every earlier
window through one summary key and value a chunk. The pooling
(:func:`eva_summaries`) runs under the scope ``moolib.lm.eva_summary``;
the two key sets are two calls of the one attention call site, both
under ``moolib.lm.attn_core`` with their row statistics as outputs
(``return_lse``), the local one causal with (episode, window) as its
segment id (and W as its reach, which hides nothing more and lets the
kernels walk a window's key blocks only), the summaries' with the ids as
group and rank
(``rank_bits``: an equal episode and an earlier window), so the flash
kernels skip every tile outside a window and every summary tile of a
window not yet past; ``ops.attention.merge_attention`` then makes the
one softmax of the two results, exactly, under ``moolib.lm.eva_merge``.
No ``[T, T]`` or ``[T, T / c]`` array is built on the flash path.
:func:`eva_pair_counts` counts what a block reads (``eva_local_pairs``,
``eva_summary_pairs``, ``eva_chunks_cut``, every block's, in the step's
metrics).

With ``cca`` (:class:`Cca`; compressed convolutional attention with
grouped heads, arXiv:2510.04476, as ZAYA1 ships it) queries, keys and
values are computed in two compressed widths, ``H D`` and ``G D`` (``H``
query heads on ``G`` key/value heads of ``D``, query head ``i`` on
key/value head ``i // (H / G)``), and mixed over time before the core:

    qt = x W_q  [T, H D];   kt = x W_k  [T, G D]
    v  = [x_t W_v1 | x_{t-1} W_v2]       half of the key/value heads are
                                         of the token itself, half of the
                                         token before it
    c  = conv1(conv0([qt | kt]))         conv0: causal, depthwise,
         ``time0`` taps a channel, a bias; conv1: causal, ``time1`` taps,
         one group of ``D`` channels a head, each tap a ``D x D`` matrix,
         a bias; nothing between them or after
    m_i = (qt_i + kt_g(i)) / 2           the query-key mean
    q_i = c^q_i + m_i;   k_g = c^k_g + mean over i in g of m_i
    qh_i = sqrt(D) q_i / |q_i|;   kh_g = tau_g sqrt(D) k_g / |k_g|
    rotary on the first ``D x partial_rotary_factor`` dimensions of qh, kh
    o = causal softmax(qh kh^T D^-1/2) v;   y = o W_o

``tau`` ``[G]`` is learned and starts at 1; the normalisation is float32
with l2norm's eps 1e-6. The four input projections, the shift and ``W_o``
run under ``moolib.lm.cca_proj``; both convolutions
(:func:`causal_conv`, :func:`grouped_causal_conv`: one boundary rule),
the mean, the normalisation, the temperature and the rotary under
``moolib.lm.cca_mix``; the core under ``moolib.lm.attn_core`` on the one
attention call site. **The episode rule** is the delta rule's: a tap of
either convolution that would reach an earlier episode reads zero, and so
does the shifted value at an episode's first position
(:func:`previous_row`). Such a block is a softmax block: its context is
the unroll, what lies before the call's first position reads as zero and
nothing is carried from call to call. ``cca_taps_cut``
(:func:`cca_taps_cut`, every block's) counts the taps and shifted values
that read zero, in the step's metrics.

With ``qk_norm`` a softmax kind with dense projections normalises every
query and key head before the rotary, ``q <- rms_head(q) * g_q``, ``k <-
rms_head(k) * g_k``, one gain ``[D]`` each a layer (Qwen3's attention).

**Block diffusion** (``diffusion``, :class:`Diffusion`; the model's, not a
kind's: BD3-LMs, arXiv:2503.09573, as SDAR trains it, arXiv:2510.06303).
The policy's action is a *denoising step*, which reveals a set of tokens
of one block at once. ``obs`` is then a dict, ``{"tokens", "reveal_step"}``
``[L, B]``: a sequence of ``L = D (N + 1)`` tokens in blocks of ``D``, and
for each token of blocks ``0..N-1`` the step ``r`` in ``[0, S)`` of its
block at which the sampler revealed it; ``done`` lies on the **step axis**,
``[S N + 1, B]``, frame ``u = S b + tau`` the state before step ``tau`` of
block ``b`` and the last frame block ``N``, all masked, which gives the
bootstrap value alone; an episode may begin at a block's first step only
(``done[S b]``). The stack runs over ``1 + S`` copies of the sequence,
``(1 + S) L`` rows, copy-major:

- row ``(clean, i)`` reads ``E[x_i]``; row ``(tau, i)`` ``E[x_i]`` where
  ``r_i < tau`` and ``E[mask_id]`` otherwise (block ``N``: the mask in every
  copy); a row's position is ``i`` in every copy;
- row ``(c, i)`` sees key row ``(c', j)`` iff both are of one episode and
  either ``c'`` is the clean copy and ``j``'s block lies before ``i``'s, or
  ``c' = c`` and the two blocks are one: in both directions, under one
  softmax. That is what a sampler computes at step ``tau`` of block ``b``:
  the finished blocks as they were computed clean, and its own
  half-revealed block;
- norms, projections, router and experts act on every row alike;
- token ``i < D N`` is scored in row ``(r_i, i)``: the call returns
  ``logits`` ``[D N, B, A]``, a row a token, and ``baseline`` ``[L, B]``,
  a value a token and block ``N``'s ``D`` rows of copy 0 last, whose mean
  the loss takes for the bootstrap value
  (:func:`moolib_tpu.learner.impala_loss` with ``action_step``).

No kernel is the mask's own: the copies ride as further grouped query
heads of the clean copy's key/value heads, one flash call with the ids
``episode << bits | block`` on both axes as group and rank (a key is seen
where its block is *strictly* earlier, ``rank_bits``; the kernels skip
every tile above the block diagonal) under ``moolib.lm.attn_core`` with its
row statistics, then the ``D x D`` scores of a row's own block against its
own copy's keys and :func:`~moolib_tpu.ops.attention.merge_attention`
under ``moolib.lm.blockdiff_local`` (:func:`blockdiff_attention`). The
copies' inputs and the choice of the scored rows run under
``moolib.lm.blockdiff_rows``. The step's metrics carry ``blockdiff_rows``
(rows through the stack), ``blockdiff_masked_inputs``,
``blockdiff_scored_tokens``, ``blockdiff_steps`` ((block, step) pairs that
reveal a token) and ``blockdiff_pairs`` (visible (query row, key row)
pairs a query head, every block's: :func:`blockdiff_counts`). Built for
one stream, softmax kinds with dense projections and no window, one
prediction head.

Without ``rope`` (null) a softmax kind has no position encoding at all;
with ``output_gate`` its heads' output is multiplied by ``sigmoid(x
W_g)``, elementwise over ``heads x head_dim``, before ``W_o``.

With ``delta`` (:class:`Delta`; the gated delta rule with a per-channel
decay, Kimi Delta Attention, arXiv:2510.26692) a kind has no softmax, no
window and no rotary. A head has a state ``S`` [D, D], float32;
``conv`` is a causal depthwise convolution over time of ``conv_size``
taps (:func:`causal_conv`):

    q_t = l2norm(silu(conv(x W_q)))   k_t = l2norm(silu(conv(x W_k)))
    v_t = silu(conv(x W_v))
    g_t = -exp(A) softplus(x W_f1 W_f2 + dt_bias)   [D] a head, <= 0
    beta_t = 2 sigmoid(x W_b)      (1 sigmoid without ``allow_neg_eigval``)
    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = D^-1/2 S_t^T q_t
    y_t = [rms_head(o_t) * sigmoid(x W_g1 W_g2)] W_o

``W_f1``, ``W_g1`` are ``[d, gate_rank]``; ``rms_head`` has one gain
``[D]`` for all heads; l2norm's eps is 1e-6. The projections, the
convolutions, both gates and the output run under
``moolib.lm.kda_proj``; the recurrence, in chunks and with no loop over
positions (:mod:`moolib_tpu.ops.delta_rule`), under ``moolib.lm.kda_core``.
**The episode rule**: a position reads nothing of an earlier episode. At
an episode's first position ``S_{t-1}`` is zero and the convolution's taps
that would reach an earlier episode read zeros. At the call's first
position, unless ``done[0]``, ``S_{-1}`` and the ``conv_size - 1`` rows
of ``[x W_q | x W_k | x W_v]`` before it come from the state handed in
(``[B, heads, D, D]`` and ``[B, conv_size - 1, 3 heads D]``; a repeated
entry's blocks on the axis after ``B``), and the call returns the same
after its last position, rows of an earlier episode than the last
position's as zeros. A softmax block in the same stack still has the
unroll for its context: the repo has no key/value cache, so what a state
carries across calls is the delta-rule blocks' part alone. The step's
metrics carry ``kda_state_resets`` and ``kda_chunks_cut`` (positions at
which the state is dropped, chunks of the recurrence that hold two
episodes: :func:`kda_boundary_counts`, every block's),
``kda_log_decay_min`` (the most negative sum of ``g`` over one chunk)
and ``kda_state_rms`` (of the state handed on).

``norm_unit_offset``: every RMS norm's gain is ``1 + scale``, its
parameter starting at 0. ``num_pred_heads`` n > 1: the head is ``hidden ->
n x vocab``, head-major; head 0's columns are the policy's logits and head
``i`` of position ``t`` is asked for token ``t + 1 + i``: the further
heads' cross-entropy, over the (position, head) pairs whose token lies in
the position's episode, is sown as ``mtp_loss`` with its count
``mtp_positions``, the seam a multi-token-prediction module feeds (a
model has one or the other). A stack needs no sparse layer: the expert
layers' counters are then absent from the step's metrics.

MLP kinds. ``sparse``: gated experts through
:func:`moolib_tpu.parallel.moe.moe_dropless`, scored here (one matrix,
:func:`~moolib_tpu.parallel.moe.linear_scores`, or the MLP router below)
and chosen as ``router`` says (softmax top-k; or sigmoid scores, a
selection bias that takes no gradient, scaled gates), with a shared
expert beside them where ``shared_expert_size`` is set. ``dense``: one gated MLP of
``intermediate_size``.

**A router with a state through the depth** (:class:`Router` with
``hidden_size`` r; ZAYA1's): the scores are not one matrix of the layer's
but an MLP's, whose r-wide state ``z_l`` layer ``l + 1`` reads:

    z_l = h W_rd + b_rd + gamma_l * z_{l-1}      float32, its products at
                                                 HIGHEST precision; gamma
                                                 [r] starts at 1; z before
                                                 the stack's first layer
                                                 is 0
    u = rms_r(z_l);  a1 = gelu(u W_1 + b_1);  a2 = gelu(a1 W_2 + b_2)
    p = softmax(a2 W_3)  over ``num_experts + skip_choices`` columns
    e = argmax(p + beta)  (``selection_bias``; top-``top_k`` in general)
    y = p_e * Expert_e(h)  where e is an expert held here; 0 where it is
        held elsewhere or, past ``num_experts``, **no expert at all**

``renormalize`` false leaves the gate ``p_e`` as it is: with one expert a
token a renormalised gate is 1 and the router learns nothing
(:func:`moe_dropless`). The MLP runs under ``moolib.moe.router_mlp``.
The blocks then carry ``(x, z)``: through :func:`_blocks`, the scan over
stacked blocks and a rebuilt block alike; a stack's first block is handed
zeros, so every block has a ``gamma`` and the first one's has no effect.
``moe_tokens_skipped``, ``moe_gate_mean`` (the mean chosen probability)
and ``router_state_rms`` (of ``z`` after the last block) join the step's
metrics.

``tie_embeddings``: the head is the embedding's rows held, transposed;
the model has no ``head`` leaf and the one matrix takes both gradients.

``mtp`` adds one multi-token-prediction module (DeepSeek-V3's form) after
the stack: from the last block's output ``h_t`` and the next token's
embedding, ``u_t = [rms(e_{t+1}) ; rms(h_t)] W_eh``, one more block of the
kinds it names, a norm of its own, the shared embedding and head: logits
for token t+2. Its cross-entropy against ``obs[t+2]``, over the positions
whose two next tokens lie in their episode, is sown with the count of
those positions; :func:`learn_apply` hands both to the loss
(``mtp_loss``, ``mtp_positions``).

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
worst-case buffer) and of the stream mixing (how far the remix matrices'
rows and columns are from summing to 1, entries at the clip) are sown
into ``intermediates``; :func:`learn_apply` gives the learner the
three-element ``apply_fn`` that reduces them over layers into the step's
metrics.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..ops import attention as attn_ops
from ..ops import delta_rule, hyper_mix
from ..parallel.moe import linear_scores, moe_dropless
from .transformer import (attend, hyper_coefficients, hyper_read,
                          hyper_residual_block, residual_block,
                          segment_ids_from_done, sown_dicts)

__all__ = [
    "AttentionKind",
    "Cca",
    "DecoderLM",
    "Delta",
    "Diffusion",
    "Eva",
    "Latent",
    "Residual",
    "Rope",
    "Router",
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
    # The share of a head (of latent attention's ``rope`` part) that is
    # turned, its first dimensions; the rest passes as it is.
    partial_rotary_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class Latent:
    """The sizes of latent attention (the keys of a DeepSeek-style
    config): both low ranks and the split of a head. ``softmax_scale``:
    what the scores are multiplied by, where it is not ``(nope +
    rope)^-1/2`` (under YaRN, that times ``mscale^2``)."""

    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class Eva:
    """Chunk-summary attention (EVA as EvaByte ships it): the kind's
    ``window`` is then a *block* of positions and not a sliding reach, and
    every ``chunk_size`` positions have one learned summary key and
    value."""

    chunk_size: int


@dataclasses.dataclass(frozen=True)
class Delta:
    """The gated delta rule as a token mixer (the keys of a
    ``linear_attn_config`` and the ``kda_*`` keys beside it): the heads
    held here and their size (key and value alike), the taps of the causal
    depthwise convolutions, the rank of the two low-rank gates, and whether
    ``beta`` runs to 2 (a transition with a negative eigenvalue) or to
    1."""

    num_heads: int
    head_dim: int
    conv_size: int
    gate_rank: int
    allow_neg_eigval: bool


@dataclasses.dataclass(frozen=True)
class Cca:
    """Compressed convolutional attention (the ``cca_time*`` keys of a
    config): the taps of the depthwise convolution and of the grouped one
    that follows it. The two compressed widths are the heads': ``num_heads
    x head_dim`` for the queries, ``num_kv_heads x head_dim`` for keys and
    values."""

    time0: int
    time1: int


@dataclasses.dataclass(frozen=True)
class Diffusion:
    """Block diffusion (module docstring): the tokens of a block, the
    denoising steps a block is revealed in, and the row of the embedding
    that stands for a token not yet revealed."""

    block: int
    steps: int
    mask_id: int


@dataclasses.dataclass(frozen=True)
class AttentionKind:
    window: Optional[int]  # None: full causal attention
    rope: Optional[Rope]  # None: no position encoding at all
    latent: Optional[Latent] = None  # None: three dense projections
    eva: Optional[Eva] = None  # windows as blocks, read through summaries
    # the softmax kind's output times sigmoid(x W_g), elementwise
    output_gate: bool = False
    delta: Optional[Delta] = None  # no softmax: the delta rule's state
    cca: Optional[Cca] = None  # queries and keys mixed by two convolutions
    # dense projections' query and key heads through an RMS norm of their
    # own, one gain [head_dim] each, before the rotary
    qk_norm: bool = False


@dataclasses.dataclass(frozen=True)
class Residual:
    """A residual skeleton with several streams (the ``hc_*`` keys of a
    config), see :func:`~moolib_tpu.models.transformer.hyper_coefficients`:
    how many streams, the Sinkhorn iterations and their ``eps``, and the
    clip of the remix matrix's logits."""

    streams: int
    sinkhorn_iters: int
    eps: float
    res_clamp: Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Router:
    """How a sparse MLP scores and chooses (``moe_dropless``'s
    arguments): ``selection_bias`` adds a parameter, one a choice, to the
    scores for the choice alone. ``hidden_size`` says where the scores
    come from: None, one matrix of the layer's own; a number, an MLP of
    that width whose state goes from layer to layer through the depth
    (module docstring). ``skip_choices`` says how wide they are: that many
    columns past ``num_experts``, each a choice that is no expert.
    ``renormalize`` false leaves the gates as the chosen scores, which a
    router of one expert a token needs to learn at all."""

    scoring: str = "softmax"
    selection_bias: bool = False
    gate_scale: float = 1.0
    hidden_size: Optional[int] = None
    skip_choices: int = 0
    renormalize: bool = True


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
    """x [T, B, H, D]; cos/sin [T, R], float32, R <= D. The half-split
    form over the first R dimensions, ``x * cos + rotate_half(x) * sin``;
    the others pass as they are."""
    rot = cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [_rotary(x[..., :rot], cos, sin), x[..., rot:]], axis=-1
        )
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    out = x32 * cos[:, None, None] + rotated * sin[:, None, None]
    return out.astype(x.dtype)


def _rotary_tables(rope: Rope, positions, dim: int):
    """cos and sin [T, R], float32, of the angles the rotated dimensions
    turn by at ``positions`` (each frequency twice: the half-split form),
    times the kind's ``attention_factor``. ``dim`` is the head (or latent
    attention's ``rope`` part); ``R = dim * partial_rotary_factor`` of it
    are turned, with the frequencies of a head of ``R``, and
    :func:`_rotary` leaves the rest alone. At a factor of 1, ``R =
    dim``."""
    dim = int(dim * rope.partial_rotary_factor)
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        rope_inv_freq(rope, dim), jnp.float32
    )
    angle = jnp.concatenate([angle, angle], axis=-1)
    return (jnp.cos(angle) * rope.attention_factor,
            jnp.sin(angle) * rope.attention_factor)


def _dense(name: str, width: int, dtype):
    return nn.Dense(width, use_bias=False, dtype=dtype, name=name)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale``; with ``unit_offset`` the
    parameter is the gain's distance from 1, ``* (1 + scale)``, and starts
    at 0."""

    eps: float
    dtype: jnp.dtype
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.unit_offset else (
            nn.initializers.ones)
        scale = self.param("scale", init, (x.shape[-1],))
        if self.unit_offset:
            scale = 1.0 + scale
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
    norm_eps: float = 1e-6  # of the query/key norm
    norm_unit_offset: bool = False
    # block diffusion: the rows are the copies, the ids group and rank
    diffusion: Optional[Diffusion] = None

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        T, B, _ = x.shape
        H, Hkv, D = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(name, heads):
            y = _dense(name, heads * D, self.dtype)(x).reshape(T, B, heads, D)
            if self.kind.qk_norm and name != "v":
                y = RMSNorm(
                    self.norm_eps, self.dtype, self.norm_unit_offset,
                    name=name + "_norm",
                )(y)
            return y

        with jax.named_scope("moolib.lm.attn_proj"):
            turn = lambda t: t  # noqa: E731  (no position encoding)
            if self.kind.rope is not None:
                cos, sin = _rotary_tables(self.kind.rope, positions, D)
                turn = lambda t: _rotary(t, cos, sin)  # noqa: E731
            q, k, v = turn(proj("q", H)), turn(proj("k", Hkv)), proj("v", Hkv)
        if self.diffusion is not None:
            o = blockdiff_attention(
                q, k, v, seg_bt, self.diffusion, backend=self.backend,
                block=self.block,
            )
        else:
            with jax.named_scope("moolib.lm.attn_proj"):
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
        with jax.named_scope("moolib.lm.attn_proj"):
            if self.kind.output_gate:
                o = o * jax.nn.sigmoid(_dense("gate", H * D, self.dtype)(x))
            return _dense("o", x.shape[-1], self.dtype)(o)


def blockdiff_bits(L: int, spec: Diffusion) -> int:
    """The low bits of a block-diffusion id that hold the block's index."""
    return max(1, (L // spec.block).bit_length())


def blockdiff_ids(done, L: int, spec: Diffusion):
    """``[B, L]`` int32, ``episode << bits | block`` of every token, from
    ``done`` on the step axis ``[S N + 1, B]``: an episode begins at a
    block's first step (``done[S b]``; frame ``S N`` is block ``N``'s), and
    a token is of its block's episode. Neither part ever decreases along
    the sequence, as the flash kernels' skipping by ``rank_bits`` needs."""
    episode = segment_ids_from_done(done[::spec.steps])  # [B, N + 1]
    block = jnp.arange(L) // spec.block
    return (episode[:, block] << blockdiff_bits(L, spec)) | block


def blockdiff_counts(ids, reveal_lb, spec: Diffusion) -> dict:
    """What one call under block diffusion runs, counted from the ids
    ``[B, L]`` (:func:`blockdiff_ids`) and the reveal steps ``[L, B]``,
    int32: ``blockdiff_rows`` rows through the stack;
    ``blockdiff_masked_inputs`` of them that read the mask's embedding;
    ``blockdiff_scored_tokens``; ``blockdiff_steps`` (block, step) pairs
    that reveal a token; ``blockdiff_pairs`` the (query row, key row)
    pairs one query head of one block sees: for a row of block ``b`` the
    clean rows of its episode's blocks before ``b`` and its own copy's
    ``D`` rows of ``b``, by cumulative counts and no score matrix."""
    B, L = ids.shape
    D, S = spec.block, spec.steps
    acted = L - D
    bits = blockdiff_bits(L, spec)
    group = (ids >> bits) << bits
    first = jax.vmap(lambda a, x: jnp.searchsorted(a, x, side="left"))
    # clean rows of the episode's earlier blocks, and the block's own D
    seen = first(ids, ids) - first(ids, group) + D
    reveal = reveal_lb[:acted].T.reshape(B, -1, D)
    steps = sum(
        jnp.sum(jnp.any(reveal == tau, axis=-1)) for tau in range(S)
    )
    # copy tau masks what its step and the later ones reveal, and block N
    masked = sum(jnp.sum(reveal >= tau) for tau in range(S)) + S * D * B
    return {
        "blockdiff_rows": jnp.asarray((1 + S) * L * B, jnp.int32),
        "blockdiff_masked_inputs": masked.astype(jnp.int32),
        "blockdiff_scored_tokens": jnp.asarray(acted * B, jnp.int32),
        "blockdiff_steps": steps.astype(jnp.int32),
        "blockdiff_pairs": ((1 + S) * jnp.sum(seen)).astype(jnp.int32),
    }


def blockdiff_attention(q, k, v, ids, spec: Diffusion, *, backend: str,
                        block: int):
    """The attention core under block diffusion (module docstring). ``q``
    ``[C L, B, H, D]``, ``k`` and ``v`` ``[C L, B, Hkv, D]``, the ``C = 1 +
    S`` copies one after the other, the clean one first; ``ids`` ``[B, L]``
    (:func:`blockdiff_ids`). Returns ``[C L, B, H D]``.

    The copies ride as further grouped query heads, ``[B, Hkv (C G), L,
    D]``, on the clean copy's ``[B, Hkv, L, D]``: one call of the
    attention call site reads every row's earlier blocks (group = episode,
    rank = block, strictly lower), and the rows' own blocks are ``L / D``
    products of ``D x D`` against the row's own copy, merged with the
    call's result by the two row statistics."""
    C, Db = spec.steps + 1, spec.block
    rows, B, H, D = q.shape
    Hkv, L = k.shape[2], rows // C
    G, n = H // Hkv, L // Db
    with jax.named_scope("moolib.lm.attn_proj"):
        # [C L, B, heads, D] -> [B, Hkv, C, (G,) L, D]
        q = q.reshape(C, L, B, Hkv, G, D).transpose(2, 3, 0, 4, 1, 5)
        k, v = (
            t.reshape(C, L, B, Hkv, D).transpose(2, 3, 0, 1, 4)
            for t in (k, v)
        )
    with jax.named_scope("moolib.lm.attn_core"):
        earlier = attend(
            q.reshape(B, H * C, L, D), k[:, :, 0], v[:, :, 0], ids,
            kv_seg_bt=ids, causal=False,
            rank_bits=blockdiff_bits(L, spec), return_lse=True,
            backend=backend, block_q=block, block_k=block,
        )
    with jax.named_scope("moolib.lm.blockdiff_local"):
        scores = jnp.einsum(
            "bhcgnqd,bhcnkd->bhcgnqk", q.reshape(B, Hkv, C, G, n, Db, D),
            k.reshape(B, Hkv, C, n, Db, D),
            preferred_element_type=jnp.float32,
        ) * D ** -0.5
        lse = jax.nn.logsumexp(scores, axis=-1)
        own = jnp.einsum(
            "bhcgnqk,bhcnkd->bhcgnqd", jnp.exp(scores - lse[..., None]),
            v.reshape(B, Hkv, C, n, Db, D).astype(jnp.float32),
        )
        o = attn_ops.merge_attention(
            *earlier, own.reshape(B, H * C, L, D).astype(v.dtype),
            lse.reshape(B, H * C, L),
        )
    with jax.named_scope("moolib.lm.attn_proj"):
        return o.reshape(B, Hkv, C, G, L, D).transpose(
            2, 4, 0, 1, 3, 5
        ).reshape(rows, B, H * D)


def eva_ids(seg_bt, T: int, window: int, chunk: int):
    """The ids both calls of chunk-summary attention mask by, as
    ``group << bits | rank`` with group = episode and rank = window
    (:mod:`moolib_tpu.ops.attention`, ``rank_bits``): ``ids_q`` [B, T] of
    the positions, ``ids_k`` [B, ceil(T / chunk)] of the chunks (the
    episode of a chunk's last position, the window it lies in), ``own``
    [B, chunks, chunk] the positions of a chunk that are of its last
    position's episode, and ``bits``. Equal ``ids_q`` is "my episode and my
    window"; an ``ids_k`` of equal group and lower rank "a chunk of my
    episode in an earlier window". Positions past ``T`` (a last chunk cut
    short) are of no chunk."""
    n = -(-T // chunk)
    bits = max(1, (-(-T // window)).bit_length())
    pos = jnp.arange(n * chunk)
    seg = jnp.pad(seg_bt, ((0, 0), (0, n * chunk - T)), mode="edge")
    ids_q = (seg_bt << bits) | (pos[:T] // window)
    seg_c = seg.reshape(-1, n, chunk)
    ids_k = (seg_c[:, :, -1] << bits) | (pos[::chunk] // window)
    own = jnp.logical_and(
        seg_c == seg_c[:, :, -1:], (pos < T).reshape(n, chunk)
    )
    return ids_q, ids_k, own, bits


def eva_summaries(k, v, own, phi, mu):
    """One summary key and value a chunk. ``k`` [B, H, T, D], ``v`` [B, H,
    T, Dv], ``own`` [B, n, c] (:func:`eva_ids`), ``phi`` and ``mu`` [H, D]:

        a_s = softmax over the chunk's own positions s of (k_s . phi) D^-1/2
        kt  = sum_s a_s k_s + mu;    vt = sum_s a_s v_s

    in float32, returned [B, H, n, D] and [B, H, n, Dv] in the dtypes that
    came. Written as multiplies and sums over the chunk, not as products:
    a chunk is 16 rows, and XLA fuses each into one pass over ``k`` or
    ``v``."""
    B, H, T, D = k.shape
    n, c = own.shape[1:]

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, n * c - T), (0, 0)))
        return x.astype(jnp.float32).reshape(B, H, n, c, x.shape[-1])

    kc, vc = chunks(k), chunks(v)
    s = jnp.sum(kc * phi.astype(jnp.float32)[None, :, None, None], axis=-1)
    a = jax.nn.softmax(
        jnp.where(own[:, None], s * D ** -0.5, -jnp.inf), axis=-1
    )[..., None]
    kt = jnp.sum(a * kc, axis=-2) + mu.astype(jnp.float32)[None, :, None]
    return kt.astype(k.dtype), jnp.sum(a * vc, axis=-2).astype(v.dtype)


def kda_boundary_counts(seg_bt) -> dict:
    """What the episode boundaries ask of one delta-rule block, counted
    from the ids ``[B, T]``, int32: ``kda_state_resets`` positions at which
    the state is dropped (the call's first among them where it does not
    continue the state handed in), ``kda_chunks_cut`` chunks of the
    recurrence that hold positions of two episodes."""
    T = seg_bt.shape[1]
    C = delta_rule.chunk_of(T)
    seg = jnp.pad(seg_bt, ((0, 0), (0, -T % C)), mode="edge")
    seg = seg.reshape(seg.shape[0], -1, C)
    return {
        "kda_state_resets": jnp.sum(seg_bt[:, -1]),
        "kda_chunks_cut": jnp.sum(seg[:, :, 0] != seg[:, :, -1]),
    }


def eva_pair_counts(seg_bt, T: int, window: int, chunk: int) -> dict:
    """What one block of chunk-summary attention reads, counted from the
    episode boundaries, int32: ``eva_local_pairs`` (query, key) pairs of
    one episode and window with the key not after the query,
    ``eva_summary_pairs`` (query, chunk) pairs with the chunk of the
    query's episode and an earlier window, ``eva_chunks_cut`` chunks that
    straddle a boundary."""
    ids_q, ids_k, own, bits = eva_ids(seg_bt, T, window, chunk)
    first = jax.vmap(lambda a, x: jnp.searchsorted(a, x, side="left"))
    local = jnp.arange(T) - first(ids_q, ids_q) + 1
    group = (ids_q >> bits) << bits
    summaries = first(ids_k, ids_q) - first(ids_k, group)
    whole = (jnp.arange(own.shape[1]) + 1) * chunk <= T
    return {
        "eva_local_pairs": jnp.sum(local),
        "eva_summary_pairs": jnp.sum(summaries),
        "eva_chunks_cut": jnp.sum(jnp.logical_and(~own[:, :, 0], whole)),
    }


class _EvaAttention(nn.Module):
    """Chunk-summary attention; see the module docstring. Two calls of the
    one attention call site, merged by their row statistics."""

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
            return _dense(name, heads * D, self.dtype)(x).reshape(
                T, B, heads, D
            )

        phi = self.param("phi", nn.initializers.normal(D ** -0.5), (Hkv, D))
        mu = self.param("mu", nn.initializers.zeros, (Hkv, D))
        with jax.named_scope("moolib.lm.attn_proj"):
            cos, sin = _rotary_tables(self.kind.rope, positions, D)
            q = _rotary(proj("q", H), cos, sin)
            k = _rotary(proj("k", Hkv), cos, sin)
            v = proj("v", Hkv)
            q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        with jax.named_scope("moolib.lm.eva_summary"):
            ids_q, ids_k, own, bits = eva_ids(
                seg_bt, T, self.kind.window, self.kind.eva.chunk_size
            )
            kt, vt = eva_summaries(k, v, own, phi, mu)
        blocks = dict(
            backend=self.backend, block_q=self.block, block_k=self.block,
            return_lse=True,
        )
        with jax.named_scope("moolib.lm.attn_core"):
            # a key of the query's own window lies less than a window
            # back: saying so changes no mask, and the flash kernels then
            # walk the key blocks a window can reach and not the sequence
            local = attend(q, k, v, ids_q, window=self.kind.window, **blocks)
            earlier = attend(
                q, kt, vt, ids_q, kv_seg_bt=ids_k, causal=False,
                rank_bits=bits, **blocks,
            )
        with jax.named_scope("moolib.lm.eva_merge"):
            o = attn_ops.merge_attention(*local, *earlier)
        with jax.named_scope("moolib.lm.attn_proj"):
            o = o.transpose(2, 0, 1, 3).reshape(T, B, H * D)
            return _dense("o", x.shape[-1], self.dtype)(o)


def _episode_taps(x, seg_tb, tail, K: int):
    """The boundary rule of every convolution over time here. ``x`` [T, B,
    C] after the ``K - 1`` rows of ``tail`` [B, K - 1, C] (of episode 0);
    returns the rows and their ids so joined, float32, and ``tap(j)``: the
    row ``K - 1 - j`` positions before each position, [T, B, C], zero
    where that row is of another episode."""
    T = x.shape[0]
    rows = jnp.concatenate(
        [tail.transpose(1, 0, 2).astype(jnp.float32), x.astype(jnp.float32)]
    )
    seg = jnp.pad(seg_tb, ((K - 1, 0), (0, 0)))

    def tap(j):
        return jnp.where(
            (seg[j:j + T] == seg_tb)[..., None], rows[j:j + T], 0.0
        )

    return rows, seg, tap


def grouped_causal_conv(x, w, seg_tb):
    """:func:`causal_conv`'s grouped sibling, with nothing before the
    call's first row: ``x`` [T, B, G, D]; ``w`` [K, G, D, D], tap ``K - 1``
    on the position itself; every tap mixes the ``D`` channels of one
    group by a matrix of its own, ``y_t[g] = sum_j x_{t-(K-1-j)}[g] @ w[j,
    g]``. A tap that would reach another episode, or before the call,
    reads zero. Returns ``y`` [T, B, G, D] float32."""
    T, B, G, D = x.shape
    K = w.shape[0]
    _, _, tap = _episode_taps(
        x.reshape(T, B, G * D), seg_tb, jnp.zeros((B, K - 1, G * D)), K
    )
    return sum(
        jnp.einsum(
            "tbgd,gde->tbge", tap(j).reshape(T, B, G, D),
            w[j].astype(jnp.float32),
        ) for j in range(K)
    )


def previous_row(x, seg_tb):
    """Row ``t - 1`` of ``x`` [T, B, C] at row ``t``, float32: zero at an
    episode's first position and at the call's (the convolutions' rule,
    for a shift by one)."""
    tail = jnp.zeros((x.shape[1], 1, x.shape[2]))
    return _episode_taps(x, seg_tb, tail, 2)[2](0)


def cca_taps_cut(seg_bt, kind: Cca):
    """What the episode boundaries (and the call's first row) take from
    one block of compressed convolutional attention, counted from the ids
    ``[B, T]``, int32: (position, tap) pairs of either convolution, the
    position's own tap apart, and shifted values that read zero."""
    seg = seg_bt.T
    T = seg.shape[0]

    def cut(back):  # positions whose row `back` before is not theirs
        padded = jnp.pad(seg, ((back, 0), (0, 0)), constant_values=-1)
        return jnp.sum(padded[:T] != seg)

    return sum(
        cut(back) for taps in (kind.time0, kind.time1, 2)
        for back in range(1, taps)
    )


def causal_conv(x, w, seg_tb, tail):
    """A causal depthwise convolution over time that reads nothing across
    an episode boundary. ``x`` [T, B, C]; ``w`` [K, C], tap ``K - 1`` on
    the position itself and tap ``j`` on the one ``K - 1 - j`` before it;
    ``seg_tb`` [T, B] episode ids; ``tail`` [B, K - 1, C] the rows before
    the call's first, of episode 0 (zeros where that episode had none).
    A tap that would reach another episode reads zero. Returns ``y`` [T,
    B, C] float32 and the next call's ``tail``, float32: the last ``K -
    1`` rows, those of an earlier episode than the last row's zeroed."""
    T, K = x.shape[0], w.shape[0]
    rows, seg, tap = _episode_taps(x, seg_tb, tail, K)
    y = sum(w[j].astype(jnp.float32) * tap(j) for j in range(K))
    tail = jnp.where(
        (seg[T:] == seg[-1:])[..., None], rows[T:], 0.0
    ).transpose(1, 0, 2)
    return y, tail


class _DeltaAttention(nn.Module):
    """The gated delta rule as a token mixer (Kimi Delta Attention); see
    the module docstring. ``state``: ``(S [B, H, D, D], rows [B, K - 1,
    3 H D])``, float32; returns ``(y, state)``."""

    kind: AttentionKind
    eps: float
    dtype: jnp.dtype
    norm_unit_offset: bool = False

    @nn.compact
    def __call__(self, x, seg_bt, state):
        T, B, d = x.shape
        spec = self.kind.delta
        H, D, K = spec.num_heads, spec.head_dim, spec.conv_size
        S, rows = state

        def dense(name, width):
            return _dense(name, width, self.dtype)

        def heads(t):  # [T, B, H D] -> [B, H, T, D]
            return t.reshape(T, B, H, D).transpose(1, 2, 0, 3)

        def l2norm(t):
            return t * jax.lax.rsqrt(
                jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6
            )

        a_log = self.param("A_log", nn.initializers.zeros, (H,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (H * D,))
        with jax.named_scope("moolib.lm.kda_proj"):
            qkv = jnp.concatenate(
                [dense(n, H * D)(x) for n in ("q", "k", "v")], axis=-1
            )
            taps = jnp.concatenate([
                self.param(
                    f"conv_{n}", nn.initializers.normal(K ** -0.5), (K, H * D)
                ) for n in ("q", "k", "v")
            ], axis=-1)
            mixed, rows = causal_conv(qkv, taps, seg_bt.T, rows)
            q, k, v = (
                heads(t) for t in jnp.split(jax.nn.silu(mixed), 3, axis=-1)
            )
            q, k = l2norm(q), l2norm(k)
            decay = dense("f_b", H * D)(dense("f_a", spec.gate_rank)(x))
            g = -jnp.exp(a_log.astype(jnp.float32))[None, :, None, None] * (
                jax.nn.softplus(heads(decay.astype(jnp.float32) + dt_bias))
            )
            beta = jax.nn.sigmoid(
                dense("b", H)(x).astype(jnp.float32)
            ).transpose(1, 2, 0)
            if spec.allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("moolib.lm.kda_core"):
            o, S = delta_rule.gated_delta_rule(q, k, v, g, beta, seg_bt, S)
            self.sow("intermediates", "kda_gauges", {
                "kda_log_decay_min": delta_rule.log_decay_min(g),
                "kda_state_sq": jnp.mean(S * S),
            })
        with jax.named_scope("moolib.lm.kda_proj"):
            o = RMSNorm(
                self.eps, self.dtype, self.norm_unit_offset, name="o_norm"
            )(o.transpose(2, 0, 1, 3))
            gate = dense("g_b", H * D)(dense("g_a", spec.gate_rank)(x))
            o = o.reshape(T, B, H * D) * jax.nn.sigmoid(gate)
            return dense("o", d)(o), (S, rows)


class _CcaAttention(nn.Module):
    """Compressed convolutional attention with grouped heads; see the
    module docstring. Everything between the projections and the core is
    float32; the core reads ``qh``, ``kh`` and ``v`` in the compute
    type."""

    kind: AttentionKind
    num_heads: int
    num_kv_heads: int
    head_dim: int
    backend: str
    block: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        T, B, d = x.shape
        H, G, D = self.num_heads, self.num_kv_heads, self.head_dim
        spec = self.kind.cca
        if G % 2 or H % G:
            raise ValueError(
                "the value shift takes half of the key/value heads, and "
                f"a key/value head serves whole query heads: {H} / {G}"
            )
        seg_tb = seg_bt.T

        def dense(name, width):
            return _dense(name, width, self.dtype)

        C = (H + G) * D
        taps0 = self.param(
            "conv0", nn.initializers.normal(spec.time0 ** -0.5),
            (spec.time0, C),
        )
        bias0 = self.param("conv0_bias", nn.initializers.zeros, (C,))
        taps1 = self.param(
            "conv1", nn.initializers.normal((spec.time1 * D) ** -0.5),
            (spec.time1, H + G, D, D),
        )
        bias1 = self.param("conv1_bias", nn.initializers.zeros, (C,))
        tau = self.param("temperature", nn.initializers.ones, (G,))
        with jax.named_scope("moolib.lm.cca_proj"):
            qt, kt = dense("q", H * D)(x), dense("k", G * D)(x)
            own = dense("v_own", G * D // 2)(x)
            before = previous_row(
                dense("v_prev", G * D // 2)(x), seg_tb
            ).astype(self.dtype)
            v = jnp.concatenate([own, before], axis=-1).reshape(T, B, G, D)
        with jax.named_scope("moolib.lm.cca_mix"):
            mixed, _ = causal_conv(
                jnp.concatenate([qt, kt], axis=-1), taps0, seg_tb,
                jnp.zeros((B, spec.time0 - 1, C)),
            )
            mixed = grouped_causal_conv(
                (mixed + bias0).reshape(T, B, H + G, D), taps1, seg_tb
            ) + bias1.reshape(H + G, D)
            qt = qt.astype(jnp.float32).reshape(T, B, G, H // G, D)
            kt = kt.astype(jnp.float32).reshape(T, B, G, 1, D)
            mean = (qt + kt) / 2  # the query-key mean, a query head
            q = mixed[:, :, :H] + mean.reshape(T, B, H, D)
            k = mixed[:, :, H:] + jnp.mean(mean, axis=3)

            def l2norm(t):
                return t * (D ** 0.5) * jax.lax.rsqrt(
                    jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6
                )

            q = l2norm(q)
            k = l2norm(k) * tau.astype(jnp.float32)[:, None]
            cos, sin = _rotary_tables(self.kind.rope, positions, D)
            q = _rotary(q, cos, sin).astype(self.dtype)
            k = _rotary(k, cos, sin).astype(self.dtype)
            q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        with jax.named_scope("moolib.lm.attn_core"):
            o = attend(
                q, k, v, seg_bt, backend=self.backend,
                window=self.kind.window, block_q=self.block,
                block_k=self.block,
            )
        with jax.named_scope("moolib.lm.cca_proj"):
            o = o.transpose(2, 0, 1, 3).reshape(T, B, H * D)
            return dense("o", d)(o)


class _LatentAttention(nn.Module):
    """Latent attention in its decompressed form; see the module
    docstring. The core runs at a query/key head of ``nope + rope`` and a
    value head of ``v_head_dim``, whatever the two are."""

    kind: AttentionKind
    num_heads: int
    backend: str
    block: int
    eps: float
    dtype: jnp.dtype
    norm_unit_offset: bool = False

    @nn.compact
    def __call__(self, x, seg_bt, positions):
        T, B, _ = x.shape
        H, lat = self.num_heads, self.kind.latent
        nope, rot, dv = (
            lat.qk_nope_head_dim, lat.qk_rope_head_dim, lat.v_head_dim
        )
        def dense(name, width):
            return _dense(name, width, self.dtype)

        def norm(name):
            return RMSNorm(
                self.eps, self.dtype, self.norm_unit_offset, name=name
            )

        with jax.named_scope("moolib.lm.mla_proj"):
            c_q = norm("q_a_norm")(dense("q_a", lat.q_lora_rank)(x))
            q = dense("q_b", H * (nope + rot))(c_q).reshape(
                T, B, H, nope + rot
            )
            c_kv, k_r = jnp.split(
                dense("kv_a", lat.kv_lora_rank + rot)(x),
                [lat.kv_lora_rank], axis=-1,
            )
            kv = dense("kv_b", H * (nope + dv))(
                norm("kv_a_norm")(c_kv)
            ).reshape(T, B, H, nope + dv)
            cos, sin = _rotary_tables(self.kind.rope, positions, rot)
            q = jnp.concatenate(
                [q[..., :nope], _rotary(q[..., nope:], cos, sin)], axis=-1
            )
            # one rotary key a position, the same for every head
            k_r = _rotary(k_r[:, :, None, :], cos, sin)
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_r, (T, B, H, rot))],
                axis=-1,
            )
            v = kv[..., nope:]
            q, k, v = (t.transpose(1, 2, 0, 3) for t in (q, k, v))
        with jax.named_scope("moolib.lm.attn_core"):
            o = attend(
                q, k, v, seg_bt, backend=self.backend,
                window=self.kind.window, scale=lat.softmax_scale,
                block_q=self.block, block_k=self.block,
            )
        with jax.named_scope("moolib.lm.mla_proj"):
            o = o.transpose(2, 0, 1, 3).reshape(T, B, H * dv)
            return dense("o", x.shape[-1])(o)


class _GatedMlp(nn.Module):
    """``(silu(x W_gate) * x W_up) W_down``, no bias."""

    d_ff: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        h = jax.nn.silu(_dense("gate", self.d_ff, self.dtype)(x)) * _dense(
            "up", self.d_ff, self.dtype
        )(x)
        return _dense("down", x.shape[-1], self.dtype)(h)


class _SparseMlp(nn.Module):
    """Gated experts behind a router over ``num_experts``; ``held`` of
    them live here (see :func:`moe_dropless`). The shared expert, where
    there is one, is whole on every chip and outside the expert layer's
    own scopes."""

    num_experts: int
    held: Tuple[int, int]
    top_k: int
    d_ff: int
    buffer_rows: Optional[int]
    router: Router = Router()
    shared_d_ff: Optional[int] = None
    dtype: jnp.dtype = jnp.float32
    norm_eps: float = 1e-6  # of the MLP router's norm

    @nn.compact
    def __call__(self, x, z=None):
        """[T, B, d] -> [T, B, d]; with an MLP router, ``(x, z) -> (y,
        z)``."""
        T, B, d = x.shape
        tokens = x.reshape(T * B, d)
        init = nn.initializers.lecun_normal()
        router = self.router
        choices = self.num_experts + router.skip_choices
        if router.hidden_size is None:
            scores = linear_scores(
                tokens, self.param("router", init, (d, choices)),
                router.scoring,
            )
        elif router.scoring != "softmax":
            raise ValueError("the MLP router scores by one softmax")
        else:
            # The MLP router, float32: this layer's state from the token
            # and the state of the layer before (zeros for a stack's
            # first), and the scores [T B, choices] from it. Its products
            # are float32 in deed (HIGHEST: a TPU's default runs a float32
            # product in one bfloat16 pass): the choice is an argmax, the
            # same token id meets the first router alike wherever it
            # stands, and one rounding that tips it moves every such token
            # to another expert at once.
            def dense(name, width, bias=True):
                return nn.Dense(
                    width, use_bias=bias, name=name,
                    precision=jax.lax.Precision.HIGHEST,
                )

            hidden = router.hidden_size
            gamma = self.param("router_gamma", nn.initializers.ones, (hidden,))
            with jax.named_scope("moolib.moe.router_mlp"):
                z = dense("router_down", hidden)(x.astype(jnp.float32)) + (
                    gamma * z
                )
                u = RMSNorm(self.norm_eps, jnp.float32, name="router_norm")(z)
                for name in ("router_1", "router_2"):
                    u = jax.nn.gelu(dense(name, hidden)(u), approximate=False)
                logits = dense("router_out", choices, bias=False)(u)
                scores = jax.nn.softmax(logits.reshape(-1, choices), axis=-1)
        count = self.held[1]
        # batch_axis=0: the expert axis is a batch of matrices, not fan-in.
        expert_init = nn.initializers.lecun_normal(batch_axis=(0,))
        params = {
            "w_gate": self.param("w_gate", expert_init, (count, d, self.d_ff)),
            "w_up": self.param("w_up", expert_init, (count, d, self.d_ff)),
            "w_down": self.param("w_down", expert_init, (count, self.d_ff, d)),
        }
        select_bias = None
        if router.selection_bias:
            select_bias = self.param(
                "e_score_correction_bias", nn.initializers.zeros, (choices,),
            )
        y, aux = moe_dropless(
            params, tokens, scores, top_k=self.top_k,
            held=self.held, buffer_rows=self.buffer_rows,
            select_bias=select_bias, gate_scale=router.gate_scale,
            skip_choices=router.skip_choices, renormalize=router.renormalize,
        )
        self.sow("intermediates", "moe_router_load", aux.pop("moe_router_load"))
        self.sow("intermediates", "moe_counters", aux)
        y = y.reshape(T, B, d)
        if self.shared_d_ff is not None:
            with jax.named_scope("moolib.moe.shared"):
                y = y + _GatedMlp(self.shared_d_ff, self.dtype, name="shared")(x)
        return y if router.hidden_size is None else (y, z)


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
    router: Router
    shared_expert_size: Optional[int]
    intermediate_size: Optional[int]
    residual: Union[None, Residual, str] = None
    norm_unit_offset: bool = False
    diffusion: Optional[Diffusion] = None

    @property
    def depth_state(self) -> bool:
        """Whether the blocks hand a router's state on through the
        depth."""
        return self.router.hidden_size is not None

    def norm(self, name: str) -> RMSNorm:
        return RMSNorm(
            self.rms_norm_eps, self.compute_dtype, self.norm_unit_offset,
            name=name,
        )


class _HyperMix(nn.Module):
    """One sublayer's mixing parameters on the skeleton with several
    streams: ``phi`` [n d, n^2 + 2n], ``b`` [n^2 + 2n] and ``alpha``
    (three scales), float32, and its read side: ``streams [n, N, d] -> (h,
    streams, coef)`` as :func:`hyper_residual_block` takes it; the mixing's
    counters are sown. Which path computes it (``hyper_mix.mix_path``) is
    counted in ``residual_mix_calls_traced_total{path=}``."""

    spec: Residual
    norm_eps: float

    @nn.compact
    def __call__(self, streams):
        n, N, d = streams.shape
        k = n * n + 2 * n
        phi = self.param(
            "phi", nn.initializers.normal((n * d) ** -0.5), (n * d, k)
        )
        b = self.param("b", nn.initializers.zeros, (k,))
        alpha = self.param("alpha", nn.initializers.ones, (3,))
        spec = self.spec
        if hyper_mix.traced_path(streams.shape, streams.dtype) == "fused":
            h, streams, coef, counters = hyper_mix.read(
                streams, phi, b, alpha, self.norm_eps, spec.sinkhorn_iters,
                spec.eps, tuple(spec.res_clamp),
            )
        else:
            pre, post, res, counters = hyper_coefficients(
                streams, phi, b, alpha, norm_eps=self.norm_eps,
                sinkhorn_iters=spec.sinkhorn_iters, eps=spec.eps,
                res_clamp=spec.res_clamp,
            )
            h = hyper_read(streams, pre)
            coef = jnp.concatenate([pre, post, res.reshape(n * n, N)])
        self.sow("intermediates", "hc_counters", counters)
        return h, streams, coef


class _ResidualScale(nn.Module):
    """A sublayer's merge on the scaled skeleton: four learned vectors of
    the hidden width, ``a_r (x + b_r) + a_y (y + b_y)`` with ``x`` the
    stream and ``y`` the sublayer's output; ``a`` start at 1, ``b`` at 0,
    where it is ``x + y``. Float32, returned in the stream's type, under
    ``moolib.lm.residual_scale``."""

    @nn.compact
    def __call__(self, x, y):
        d = x.shape[-1]
        a_r, a_y = (
            self.param(n, nn.initializers.ones, (d,)) for n in ("a_r", "a_y")
        )
        b_r, b_y = (
            self.param(n, nn.initializers.zeros, (d,)) for n in ("b_r", "b_y")
        )
        with jax.named_scope("moolib.lm.residual_scale"):
            out = a_r * (x.astype(jnp.float32) + b_r) + a_y * (
                y.astype(jnp.float32) + b_y
            )
            return out.astype(x.dtype)


class _Block(nn.Module):
    kind: AttentionKind
    mlp: str
    net: _Sizes
    scanned: bool = False  # the body of a scan: returns (carry, None)

    @nn.compact
    def __call__(self, x, seg_bt, positions, state=()):
        net = self.net
        norm = net.norm
        if net.diffusion is not None and (
            self.kind.latent, self.kind.eva, self.kind.delta, self.kind.cca,
            self.kind.window,
        ) != (None,) * 5:
            raise ValueError(
                "block diffusion is built for softmax kinds with dense "
                "projections and no window"
            )
        if self.kind.qk_norm and (
            self.kind.latent, self.kind.eva, self.kind.delta, self.kind.cca,
        ) != (None,) * 4:
            raise ValueError(
                "qk_norm is the dense projections' kind's: the others "
                "normalise their queries and keys themselves, or not at all"
            )
        if self.kind.delta is not None:
            if (self.kind.latent, self.kind.eva, self.kind.window,
                    self.kind.rope) != (None,) * 4 or self.kind.output_gate:
                raise ValueError(
                    "the delta rule has no softmax: no window, rotary, "
                    "latent ranks, summaries or output gate of that kind"
                )
            attention = _DeltaAttention(
                self.kind, net.rms_norm_eps, net.compute_dtype,
                net.norm_unit_offset, name="attn",
            )
        elif self.kind.eva is not None:
            if self.kind.latent is not None or self.kind.window is None:
                raise ValueError(
                    "chunk-summary attention has dense projections and a "
                    "window"
                )
            attention = _EvaAttention(
                self.kind, net.num_heads, net.num_kv_heads, net.head_dim,
                net.attention_backend, net.attention_block,
                net.compute_dtype, name="attn",
            )
        elif self.kind.cca is not None:
            if self.kind.latent is not None or self.kind.rope is None:
                raise ValueError(
                    "compressed convolutional attention has projections of "
                    "its own and a rotary"
                )
            attention = _CcaAttention(
                self.kind, net.num_heads, net.num_kv_heads, net.head_dim,
                net.attention_backend, net.attention_block,
                net.compute_dtype, name="attn",
            )
        elif self.kind.latent is None:
            attention = _Attention(
                self.kind, net.num_heads, net.num_kv_heads, net.head_dim,
                net.attention_backend, net.attention_block,
                net.compute_dtype, net.rms_norm_eps, net.norm_unit_offset,
                net.diffusion, name="attn",
            )
        else:
            attention = _LatentAttention(
                self.kind, net.num_heads, net.attention_backend,
                net.attention_block, net.rms_norm_eps, net.compute_dtype,
                net.norm_unit_offset, name="attn",
            )
        # a router with a state through the depth: the carry is (x, z)
        z = None
        if net.depth_state:
            x, z = x
        if self.mlp == "sparse":
            sparse = _SparseMlp(
                net.num_experts, net.held, net.top_k,
                net.moe_intermediate_size, net.moe_buffer_rows, net.router,
                net.shared_expert_size, net.compute_dtype, net.rms_norm_eps,
                name="moe",
            )

            def mlp(h):
                nonlocal z
                if z is None:
                    return sparse(h)
                y, z = sparse(h, z)
                return y
        elif self.mlp == "dense":
            dense = _GatedMlp(
                net.intermediate_size, net.compute_dtype, name="mlp"
            )

            def mlp(h):
                with jax.named_scope("moolib.lm.mlp_dense"):
                    return dense(h)
        else:
            raise ValueError(
                f"unknown mlp kind {self.mlp!r}; have 'sparse', 'dense'"
            )
        def mixer(h):
            nonlocal state
            if self.kind.delta is None:
                return attention(h, seg_bt, positions)
            y, state = attention(h, seg_bt, state)
            return y

        if net.residual is None:
            out = residual_block(x, norm("norm1"), mixer, norm("norm2"), mlp)
        elif net.residual == "scaled":
            out = residual_block(
                x, norm("norm1"), mixer, norm("norm2"), mlp,
                _ResidualScale(name="scale_attn"),
                _ResidualScale(name="scale_mlp"),
            )
        else:
            out = hyper_residual_block(
                x, _HyperMix(net.residual, net.rms_norm_eps, name="hc_attn"),
                norm("norm1"), mixer,
                _HyperMix(net.residual, net.rms_norm_eps, name="hc_mlp"),
                norm("norm2"), mlp,
            )
        if z is not None:
            out = (out, z)
        if self.kind.delta is not None:
            return out, state
        return (out, None) if self.scanned else out


def _blocks(kind: AttentionKind, mlp: str, sizes: _Sizes, repeat: int,
            remat, name: str):
    """One block, or ``repeat`` of them as one scan over parameters
    stacked on a leading axis. ``remat`` is the model's ``remat_blocks``,
    the one decision of what a block keeps for the backward pass: false,
    everything; ``"cores"`` (or true), its input and, where its attention
    ran the flash kernels, its attention cores' outputs and row statistics
    (what the forward kernel alone can make, so the rebuild runs no such
    kernel); ``"input"``, its input alone, and the rebuild runs the
    forward kernels again. ``(x, seg_bt, positions) -> x``, with ``x`` the
    skeleton's carry: one stream ``[T, B, d]``, several ``[n, T, B, d]``,
    or, where the router has a state through the depth, the pair ``(x,
    z)`` with ``z`` ``[T, B, hidden]`` float32; for the delta rule, the
    one kind that carries something from call to call, ``(x, seg_bt,
    positions, state) -> (x, state)``, the state's leaves ``[B, ...]`` a
    block and ``[B, repeat, ...]`` a scan (the blocks on the axis after
    the batch's)."""
    stateful = kind.delta is not None
    cls, traced = _Block, contextlib.nullcontext
    if remat == "input":
        cls = nn.remat(_Block, prevent_cse=False)
    elif remat in (True, "cores"):
        cls = nn.remat(_Block, prevent_cse=False, policy=attn_ops.KEEP_CORES)
        traced = attn_ops.keeping_cores
    elif remat:
        raise ValueError(
            f"remat_blocks is false, 'cores' (or true) or 'input': {remat!r}"
        )
    if repeat == 1:
        block = cls(kind, mlp, sizes, name=name)
    else:
        scan = nn.scan(
            cls, variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, nn.broadcast, 1) if stateful
            else nn.broadcast,
            out_axes=1 if stateful else 0, length=repeat,
        )(kind, mlp, sizes, True, name=name)

        def block(*args):  # a stateless body's second output is None
            x, state = scan(*args)
            return (x, state) if stateful else x

    def run(*args):
        with traced():  # the body is traced inside this call
            return block(*args)

    return run


class _Mtp(nn.Module):
    """One multi-token-prediction module; see the module docstring.
    Returns its masked mean cross-entropy and the positions that entered
    it."""

    kind: AttentionKind
    mlp: str
    net: _Sizes
    remat: Union[bool, str]
    loss_rows: int

    @nn.compact
    def __call__(self, hidden, embedded, obs, seg_bt, positions, head_kernel):
        net = self.net
        T, B, d = hidden.shape
        norm = net.norm
        # Position t reads token t+1's embedding and is asked for token
        # t+2; the last two positions have no such token and are masked.
        u = _dense("eh_proj", d, net.compute_dtype)(jnp.concatenate(
            [norm("enorm")(jnp.roll(embedded, -1, axis=0)),
             norm("hnorm")(hidden)], axis=-1,
        ))
        u = _blocks(self.kind, self.mlp, net, 1, self.remat, "block")(
            u, seg_bt, positions
        )
        u = norm("final_norm")(u)
        seg = seg_bt.T  # [T, B]
        valid = jnp.logical_and(
            jnp.roll(seg, -2, axis=0) == seg,  # then t+1 is t's too
            (jnp.arange(T) < T - 2)[:, None],
        )
        target = jnp.roll(obs, -2, axis=0)
        rows = min(self.loss_rows, T * B)
        if (T * B) % rows:
            raise ValueError(
                f"{T * B} positions do not split into blocks of {rows}"
            )
        kernel = head_kernel.astype(net.compute_dtype)

        @jax.checkpoint
        def block_nll(u, target, valid):
            logp = jax.nn.log_softmax(
                (u @ kernel).astype(jnp.float32), axis=-1
            )
            nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(valid, nll, 0.0))

        total = jnp.sum(jax.lax.map(
            lambda xs: block_nll(*xs),
            (u.reshape(-1, rows, d), target.reshape(-1, rows),
             valid.reshape(-1, rows)),
        ))
        count = jnp.sum(valid).astype(jnp.float32)
        return total / jnp.maximum(count, 1.0), count


def _further_heads_nll(logits, obs, seg) -> dict:
    """The cross-entropy of the prediction heads after the policy's.
    ``logits`` [T, B, n, V] float32, head ``i`` of position ``t`` asked for
    ``obs[t + 2 + i]``; a position counts for a head if that token exists
    and lies in its episode (``seg`` [T, B] never decreases, so then every
    token between does). The mean over every (position, head) that counts,
    and their number."""
    T, n = logits.shape[0], logits.shape[2]
    logp = jax.nn.log_softmax(logits, axis=-1)
    total = count = 0.0
    for i in range(n):
        ahead = i + 2
        valid = jnp.logical_and(
            jnp.roll(seg, -ahead, axis=0) == seg,
            (jnp.arange(T) < T - ahead)[:, None],
        )
        nll = -jnp.take_along_axis(
            logp[:, :, i], jnp.roll(obs, -ahead, axis=0)[..., None], axis=-1
        )[..., 0]
        total = total + jnp.sum(jnp.where(valid, nll, 0.0))
        count = count + jnp.sum(valid).astype(jnp.float32)
    return {
        "mtp_loss": total / jnp.maximum(count, 1.0), "mtp_positions": count,
    }


class DecoderLM(nn.Module):
    """Causal, segment-masked decoder over token ids; see the module
    docstring. Build it from a configuration's JSON with
    :func:`decoder_lm`."""

    vocab_size: int  # rows of the embedding and of the head held here
    hidden_size: int
    # (attention kind, mlp kind) a block; a third element repeats it
    layers: Tuple[Tuple, ...]
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
    router: Router = Router()
    shared_expert_size: Optional[int] = None  # None: no shared expert
    intermediate_size: Optional[int] = None  # a ``dense`` MLP's width
    # The multi-token-prediction module's block, (attention kind, mlp
    # kind); None: no module. Its logits and cross-entropy are computed
    # ``mtp_loss_rows`` positions at a time and rebuilt in the backward
    # pass, so no second logits array is ever held.
    mtp: Optional[Tuple[str, str]] = None
    mtp_loss_rows: int = 1024
    # What a block keeps for the backward pass: false, everything; "cores"
    # (or true), its input and, where the flash kernels ran it, its
    # attention cores' outputs and row statistics (a block's worth of
    # ``[B, H, T, Dv]`` a call), everything else rebuilt; "input", its
    # input alone, and the rebuild runs the forward kernels again.
    remat_blocks: Union[bool, str] = False
    # The residual skeleton: None is one stream and ``x + F(norm(x))``;
    # a :class:`Residual`, several streams; "scaled", one stream whose
    # sublayers scale and shift both the stream and what they add to it.
    residual: Union[None, Residual, str] = None
    # Every RMS norm's gain as ``1 + scale``.
    norm_unit_offset: bool = False
    # The head's width in vocabularies: head ``i`` of position ``t`` is
    # asked for token ``t + 1 + i``. Head 0 is the policy; the others'
    # cross-entropy is the loss's ``mtp_loss`` term.
    num_pred_heads: int = 1
    # The head is the embedding, transposed: one matrix over the rows
    # held, which takes both gradients; no ``head`` leaf.
    tie_embeddings: bool = False
    # Block diffusion: ``obs`` is ``{"tokens", "reveal_step"}``, ``done``
    # lies on the step axis, and the stack runs over the clean sequence and
    # its masked copies (module docstring).
    diffusion: Optional[Diffusion] = None

    def _sizes(self) -> _Sizes:
        return _Sizes(
            self.num_heads, self.num_kv_heads, self.head_dim,
            self.num_experts, self.experts_held or (0, self.num_experts),
            self.top_k, self.moe_intermediate_size, self.moe_buffer_rows,
            self.rms_norm_eps, jnp.dtype(self.compute_dtype),
            self.attention_backend, self.attention_block, self.router,
            self.shared_expert_size, self.intermediate_size, self.residual,
            self.norm_unit_offset, self.diffusion,
        )

    def _copies(self, obs, done):
        """The rows a call under block diffusion runs over: the ids the
        ``1 + S`` copies read ``[(1 + S) L, B]``, each token's reveal step
        with block ``N``'s as 0 ``[L, B]``, the attention's ids ``[B, L]``
        and the rows' positions."""
        spec = self.diffusion
        tokens = obs["tokens"].astype(jnp.int32)
        reveal = obs["reveal_step"].astype(jnp.int32)
        L = tokens.shape[0]
        blocks = L // spec.block - 1
        if L % spec.block or blocks < 1 or reveal.shape != tokens.shape or (
            done.shape[0] != spec.steps * blocks + 1
        ):
            raise ValueError(
                f"block diffusion reads tokens and reveal_step [L, B] with "
                f"L = {spec.block} (N + 1) and done [{spec.steps} N + 1, B]:"
                f" tokens {tokens.shape}, reveal_step {reveal.shape}, done "
                f"{done.shape}"
            )
        acted = (jnp.arange(L) < L - spec.block)[:, None]
        rows = jnp.concatenate([tokens] + [
            jnp.where(
                jnp.logical_and(reveal < tau, acted), tokens, spec.mask_id
            )
            for tau in range(spec.steps)
        ])
        return (rows, jnp.where(acted, reveal, 0),
                blockdiff_ids(done, L, spec),
                jnp.tile(jnp.arange(L), 1 + spec.steps))

    @nn.compact
    def __call__(self, obs, done, core_state):
        embed = nn.Embed(
            self.vocab_size, self.hidden_size, dtype=self.compute_dtype,
            name="embed",
        )
        if self.diffusion is None:
            T = obs.shape[0]
            obs = obs.astype(jnp.int32)
            x = embedded = embed(obs)
            seg_bt = segment_ids_from_done(done)
            positions = jnp.arange(T)
        else:
            if (self.mtp is not None or self.num_pred_heads > 1
                    or self.residual is not None or core_state):
                raise ValueError(
                    "block diffusion is built for one stream with a plain "
                    "sum, one prediction head and no carried state"
                )
            with jax.named_scope("moolib.lm.blockdiff_rows"):
                rows, scored_in, seg_bt, positions = self._copies(obs, done)
                x = embed(rows)
            T = scored_in.shape[0]  # the tokens: the head's rows
            counters = blockdiff_counts(
                seg_bt, obs["reveal_step"].astype(jnp.int32), self.diffusion
            )
            # every block reads the same pairs
            counters["blockdiff_pairs"] *= sum(
                (repeat or [1])[0] for _, _, *repeat in self.layers
            )
            self.sow("intermediates", "blockdiff_counters", counters)
        if self.residual not in (None, "scaled") and not isinstance(
            self.residual, Residual
        ):
            raise ValueError(
                f"residual is absent, a Residual or 'scaled': "
                f"{self.residual!r}"
            )
        streams = isinstance(self.residual, Residual)
        kinds, sizes = dict(self.attention_kinds), self._sizes()
        if (sizes.depth_state or self.tie_embeddings) and (
            streams or self.mtp is not None or self.num_pred_heads > 1
        ):
            raise ValueError(
                "a router's state through the depth and a tied head are "
                "built for one stream and one prediction head"
            )
        if streams:
            if self.mtp is not None:
                raise ValueError(
                    "a multi-token-prediction module beside a residual "
                    "skeleton of several streams is not built"
                )
            # every stream starts as the token's embedding
            x = jnp.broadcast_to(x, (self.residual.streams,) + x.shape)
        if sizes.depth_state:
            # the stack's first router reads no state of a layer before it
            x = (x, jnp.zeros(
                x.shape[:2] + (self.router.hidden_size,), jnp.float32
            ))
        # a stateful entry's leaves, in the order the entries run
        states, handed_on = list(core_state), []
        for i, (attention, mlp, *repeat) in enumerate(self.layers):
            kind = kinds[attention]
            run = _blocks(
                kind, mlp, sizes, *(repeat or [1]), self.remat_blocks,
                f"block_{i}",
            )
            if kind.delta is None:
                x = run(x, seg_bt, positions)
            else:
                x, state = run(x, seg_bt, positions, tuple(states[:2]))
                states = states[2:]
                handed_on += state
        if sizes.depth_state:
            x, z = x
            self.sow("intermediates", "router_state", {
                "router_state_rms": jnp.sqrt(jnp.mean(z * z)),
            })
        if streams:
            # and the streams' sum is what the final norm reads
            x = jnp.sum(x.astype(jnp.float32), axis=0).astype(
                self.compute_dtype
            )
        heads = self.num_pred_heads
        if heads > 1 and self.mtp is not None:
            raise ValueError(
                "further prediction heads beside a multi-token-prediction "
                "module: the loss has one such term"
            )
        head = embed.attend if self.tie_embeddings else _dense(
            "head", heads * self.vocab_size, self.compute_dtype
        )
        if self.diffusion is not None:
            with jax.named_scope("moolib.lm.blockdiff_rows"):
                # a token's row is that of the copy it was revealed from
                copies = x.reshape((-1, T) + x.shape[1:])
                x = copies[1]
                for tau in range(1, self.diffusion.steps):
                    x = jnp.where(
                        (scored_in == tau)[..., None], copies[1 + tau], x
                    )
        hidden = x
        with jax.named_scope("moolib.lm.head"):
            x = sizes.norm("final_norm")(x)
            # under block diffusion the last block is the bootstrap
            # frame's: it has values and no logits
            scored = x if self.diffusion is None else (
                x[:T - self.diffusion.block])
            logits = head(scored).astype(jnp.float32)
            baseline = nn.Dense(1, name="baseline")(
                x.astype(jnp.float32)
            ).squeeze(-1)
            if heads > 1:
                # head-major columns: the first vocabulary is the policy
                logits = logits.reshape(T, -1, heads, self.vocab_size)
                self.sow("intermediates", "mtp_terms", _further_heads_nll(
                    logits[:, :, 1:], obs, seg_bt.T
                ))
                logits = logits[:, :, 0]
        eva_counters: dict = {}
        for attention, _, *repeat in self.layers:
            kind = kinds[attention]
            if kind.eva is not None:
                # every block of the entry reads the same pairs
                for name, value in eva_pair_counts(
                    seg_bt, T, kind.window, kind.eva.chunk_size
                ).items():
                    eva_counters[name] = eva_counters.get(name, 0) + (
                        (repeat or [1])[0] * value
                    )
        if eva_counters:
            self.sow("intermediates", "eva_counters", eva_counters)
        cut = [
            (repeat or [1])[0] * cca_taps_cut(seg_bt, kinds[attention].cca)
            for attention, _, *repeat in self.layers
            if kinds[attention].cca is not None
        ]
        if cut:
            self.sow(
                "intermediates", "cca_counters", {"cca_taps_cut": sum(cut)}
            )
        if handed_on:
            blocks = sum(
                (repeat or [1])[0] for attention, _, *repeat in self.layers
                if kinds[attention].delta is not None
            )
            self.sow("intermediates", "kda_counters", {
                name: blocks * value
                for name, value in kda_boundary_counts(seg_bt).items()
            })
        if self.mtp is not None:
            with jax.named_scope("moolib.lm.mtp"):
                loss, count = _Mtp(
                    kinds[self.mtp[0]], self.mtp[1], sizes,
                    self.remat_blocks, self.mtp_loss_rows, name="mtp",
                )(
                    hidden, embedded, obs, seg_bt, positions,
                    head.variables["params"]["kernel"],
                )
            self.sow("intermediates", "mtp_terms", {
                "mtp_loss": loss, "mtp_positions": count,
            })
        return (logits, baseline), tuple(handed_on) or core_state

    def initial_state(self, batch_size: int) -> Tuple:
        """``()`` for a stack without a delta-rule layer; with, a flat
        tuple of two float32 leaves an entry of ``layers`` of that kind,
        zeros: the rule's state ``[B, heads, D, D]`` and the ``conv_size -
        1`` rows before its convolutions ``[B, conv_size - 1, 3 heads D]``,
        a repeated entry's blocks stacked on the axis after ``B``."""
        kinds, state = dict(self.attention_kinds), []
        for attention, _, *repeat in self.layers:
            spec = kinds[attention].delta
            if spec is not None:
                lead = (batch_size,) + tuple(repeat)
                H, D = spec.num_heads, spec.head_dim
                state += [
                    jnp.zeros(lead + (H, D, D), jnp.float32),
                    jnp.zeros(
                        lead + (spec.conv_size - 1, 3 * H * D), jnp.float32
                    ),
                ]
        return tuple(state)


def decoder_lm(*, layers, attention_kinds, experts_held=None, router=None,
               mtp=None, residual=None, diffusion=None,
               **kwargs) -> DecoderLM:
    """A :class:`DecoderLM` from JSON-shaped arguments: ``layers`` a list
    of ``{"attention": kind, "mlp": "sparse" | "dense"}``, an entry with
    ``"repeat": n`` standing for ``n`` identical blocks run as a scan;
    ``attention_kinds`` a mapping ``kind -> {"window": int or null,
    "rope": {...} or null, "latent": {...} or absent, "eva": {...} or
    absent, "output_gate": bool or absent, "delta": {...} or absent,
    "cca": {...} or absent, "qk_norm": bool or absent}``
    whose ``rope`` holds the fields of :class:`Rope` (null: no position
    encoding; ``partial_rotary_factor`` the share of a head it turns),
    whose ``latent`` those of :class:`Latent`, whose ``eva``
    those of :class:`Eva`, whose ``delta`` those of :class:`Delta`
    (the kind is then the delta rule and has no window) and whose ``cca``
    those of :class:`Cca` (compressed convolutional attention);
    ``router`` the fields of :class:`Router` (with ``hidden_size`` an MLP
    whose state goes through the depth; ``skip_choices``;
    ``renormalize``); ``mtp`` the
    multi-token-prediction module's block, an entry like one of
    ``layers``; ``residual`` the fields of :class:`Residual`, or
    ``"scaled"`` (absent: the skeleton with one stream and a plain sum);
    ``tie_embeddings`` (a keyword like the other sizes) makes the head
    the embedding; ``diffusion`` the fields of :class:`Diffusion` (absent:
    a causal decoder whose action is the next token)."""
    kinds = tuple(
        (name, AttentionKind(
            spec.get("window"),
            Rope(**spec["rope"]) if spec.get("rope") else None,
            Latent(**spec["latent"]) if spec.get("latent") else None,
            Eva(**spec["eva"]) if spec.get("eva") else None,
            spec.get("output_gate", False),
            Delta(**spec["delta"]) if spec.get("delta") else None,
            Cca(**spec["cca"]) if spec.get("cca") else None,
            spec.get("qk_norm", False),
        ))
        for name, spec in sorted(attention_kinds.items())
    )

    def entry(l):
        pair = (l["attention"], l["mlp"])
        return pair + (l["repeat"],) if l.get("repeat", 1) > 1 else pair

    return DecoderLM(
        layers=tuple(entry(l) for l in layers),
        attention_kinds=kinds,
        experts_held=None if experts_held is None else tuple(experts_held),
        router=Router(**(router or {})),
        mtp=None if mtp is None else (mtp["attention"], mtp["mlp"]),
        residual=residual if not isinstance(residual, dict) else Residual(
            **dict(residual, res_clamp=tuple(residual["res_clamp"]))
        ),
        diffusion=None if diffusion is None else Diffusion(**diffusion),
        **kwargs,
    )


def _sum_counters(intermediates) -> dict:
    """Every expert layer's sown counters, summed over layers; the two
    loads (the fullest held expert's and the mean) averaged over them."""
    total: dict = {}
    layers = 0
    for sown in sown_dicts(intermediates, "moe_assignments_total"):
        # a scan's blocks sow one array, an element a block
        layers += next(iter(sown.values())).size
        for name, value in sown.items():
            if value.ndim:
                value = jnp.sum(value)
            total[name] = total.get(name, 0.0) + value
    for name in ("moe_load_max", "moe_load_mean", "moe_gate_mean"):
        if name in total:
            total[name] = total[name] / layers
    for name in ("mtp_loss", "eva_local_pairs", "kda_state_resets",
                 "cca_taps_cut", "router_state_rms", "blockdiff_rows"):
        for sown in sown_dicts(intermediates, name):
            total.update(sown)
    # the delta rule's gauges, an element a block: the worst, and the mean
    gauges = sown_dicts(intermediates, "kda_log_decay_min")
    if gauges:
        total["kda_log_decay_min"] = jnp.min(jnp.stack([
            jnp.min(sown["kda_log_decay_min"]) for sown in gauges
        ]))
        total["kda_state_rms"] = jnp.sqrt(jnp.mean(jnp.concatenate([
            jnp.ravel(sown["kda_state_sq"]) for sown in gauges
        ])))
    # the stream mixing's, a dict a sublayer: the worst gap, every entry
    for sown in sown_dicts(intermediates, "hc_res_clamped"):
        for name, value in sown.items():
            if name == "hc_res_clamped":
                total[name] = total.get(name, 0.0) + jnp.sum(value)
            else:
                total[name] = jnp.maximum(total.get(name, 0.0), jnp.max(value))
    return total


def learn_apply(net: DecoderLM) -> Callable:
    """The learner's ``apply_fn`` for ``net``, in the three-element
    convention of :func:`moolib_tpu.learner.impala_loss`: the third is the
    expert layers' counters summed over layers, which the loss passes
    through to the step's metrics, and, where the model has a
    multi-token-prediction module, its ``mtp_loss`` (a term of the loss,
    weighed by ``ImpalaConfig.mtp_cost``) and ``mtp_positions``."""

    def apply(params, obs, done, core_state):
        (out, state), inter = net.apply(
            params, obs, done, core_state, mutable=["intermediates"]
        )
        return out, state, _sum_counters(inter)

    return apply


def router_loads(net: DecoderLM) -> Callable:
    """``(params, obs, done) -> [layers, num_experts] int32``: the
    assignments every expert layer's router sends to each of its experts,
    held here or not, in the order the layers run (a repeated entry's
    blocks one after the other, the multi-token-prediction module's last;
    a dense block has no row). A forward pass and nothing else: for whoever
    has to know the routing of given weights on given tokens (the
    benchmark's seeding reads it)."""

    def loads(params, obs, done):
        # obs may be a dict of [.., B] leaves (block diffusion)
        columns = jax.tree_util.tree_leaves(obs)[0].shape[1]
        _, inter = net.apply(
            params, obs, done, net.initial_state(columns),
            mutable=["intermediates"],
        )
        blocks = inter["intermediates"]
        sparse = [
            blocks[f"block_{i}"] for i, layer in enumerate(net.layers)
            if layer[1] == "sparse"
        ]
        if net.mtp is not None and net.mtp[1] == "sparse":
            sparse.append(blocks["mtp"]["block"])
        return jnp.concatenate([
            b["moe"]["moe_router_load"][0].reshape(
                -1, net.num_experts + net.router.skip_choices
            )
            for b in sparse
        ])

    return loads
