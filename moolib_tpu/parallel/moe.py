"""Expert parallelism: a mixture-of-experts FFN sharded over an ``ep``
mesh axis.

The reference has no expert parallelism (SURVEY.md §2.5) — TPU-first scope
completing the mesh-axis portfolio. The design is the standard
Switch/GShard MoE mapped to XLA collectives:

- router (replicated linear) scores tokens per expert;
- each token goes to its ``top_k`` experts (top-1 = Switch, top-2 =
  GShard), subject to a fixed per-expert ``capacity`` (static shapes: XLA
  cannot compile data-dependent sizes, so overflow tokens are dropped and
  pass through the residual unchanged — the standard Switch Transformer
  behavior). Slot allocation is choice-rank-major: every token's first
  choice is seated before any second choice competes for capacity;
- ``capacity`` defaults to ``ceil(capacity_factor * T * top_k / E)`` — the
  standard knob for trading drop rate against padding waste;
- dispatch/combine are einsums against a one-hot dispatch mask; with
  experts sharded over ``ep`` (one or more experts per device) and tokens
  sharded over the same axis, the dispatch einsum IS the token->expert
  all-to-all — XLA inserts the collective from the shardings, no
  hand-written a2a (asserted in tests/test_pipeline_moe.py);
- combine scales each token's expert outputs by its (renormalized) router
  probabilities so the router receives gradients;
- aux returns the Switch load-balancing loss AND the router z-loss
  (mean logsumexp(logits)^2, ST-MoE) — add
  ``lb_weight * load_balance_loss + z_weight * router_z_loss`` to the
  training loss to keep routing balanced and logits bounded.

``moe_ffn`` is pure (call under jit/shard_map); :func:`moe_params` builds
the parameter pytree with an expert-major leading axis to shard with
``P('ep', ...)``.

Two expert layers, and when each applies:

- :func:`moe_ffn` / :func:`moe_ffn_sharded`: a fixed ``capacity`` per
  expert, one-hot ``[T, E, C]`` dispatch and combine masks, GELU experts.
  Its einsums are what GSPMD partitions over ``ep``; its masks grow with
  ``T * E * C`` and it drops what does not fit. Right for few experts and
  short ``T``.
- :func:`moe_dropless`: no token is dropped at any imbalance and no
  ``[T, E, C]`` tensor exists. Assignments are sorted by expert, the
  tokens of the experts **held here** (``held=(first, count)`` of the
  router's width) are gathered in expert order, one grouped product a
  projection runs the gated (SwiGLU) experts (on a TPU the Pallas grouped
  matmul that ships with jax, elsewhere ``jax.lax.ragged_dot``:
  :func:`resolve_grouped`), and a scatter-add combines by the
  renormalised top-k gates. With
  ``held`` a strict share it is one chip's part of an expert-parallel
  layer (what the absent experts would add is left out, and no code
  stands in for their chips); with ``held=None`` it is the whole layer.
  Its buffer may be sized for the usual routing, with the worst case's
  behind a ``lax.cond``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "moe_params", "moe_ffn", "moe_ffn_sharded", "moe_dropless",
    "resolve_grouped",
]


def moe_params(
    rng: jax.Array,
    d_model: int,
    d_hidden: int,
    num_experts: int,
    dtype=jnp.float32,
) -> Dict[str, Any]:
    """Router + expert FFN weights; expert leaves are [E, ...] (shard the
    leading axis over ``ep``)."""
    k_r, k_1, k_2 = jax.random.split(rng, 3)
    scale1 = 1.0 / jnp.sqrt(d_model)
    scale2 = 1.0 / jnp.sqrt(d_hidden)
    return {
        "router": (
            jax.random.normal(k_r, (d_model, num_experts), dtype) * scale1
        ),
        "w_up": (
            jax.random.normal(k_1, (num_experts, d_model, d_hidden), dtype)
            * scale1
        ),
        "w_down": (
            jax.random.normal(k_2, (num_experts, d_hidden, d_model), dtype)
            * scale2
        ),
    }


def moe_ffn(
    params: Dict[str, Any],
    x: jax.Array,
    capacity: Optional[int] = None,
    *,
    top_k: int = 1,
    capacity_factor: float = 1.25,
):
    """Top-``top_k`` MoE FFN. ``x``: [T, d_model] tokens; returns
    ([T, d_model], aux) where aux carries the load-balancing loss, the
    router z-loss, and the dropped-assignment fraction.

    ``capacity`` (per-expert slots) defaults to
    ``ceil(capacity_factor * T * top_k / E)``. Works replicated or with
    expert-sharded params: under jit with ``w_up``/``w_down`` sharded
    ``P('ep', None, None)``, XLA partitions the dispatch/expert/combine
    einsums over ``ep`` and inserts the collectives itself (with tokens
    sharded over the same axis, dispatch lowers to an all-to-all).
    """
    T, d_model = x.shape
    E = params["router"].shape[-1]
    if capacity is None:
        capacity = int(math.ceil(capacity_factor * T * top_k / E))
    capacity = min(capacity, T)
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]

    dispatch, combine, kept_assignments, first_oh = _dispatch_combine(
        probs, capacity, top_k, x.dtype
    )

    xe = jnp.einsum("tec,td->ecd", dispatch, x)  # [E, C, d_model]
    h = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", xe, params["w_up"].astype(x.dtype))
    )
    ye = jnp.einsum("ech,ehd->ecd", h, params["w_down"].astype(x.dtype))
    # Combine carries the gates, so the router receives gradients.
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)

    # Switch load-balancing loss on first choices: E * sum_e f_e * p_e.
    frac_tokens = jnp.mean(first_oh, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = {
        "load_balance_loss": E * jnp.sum(frac_tokens * frac_probs),
        # ST-MoE router z-loss: keeps router logits from drifting to
        # magnitudes where softmax saturates and bf16 round-trips poorly.
        "router_z_loss": jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2
        ),
        "drop_fraction": 1.0 - kept_assignments / top_k,
    }
    return y, aux


def _dispatch_combine(probs: jax.Array, capacity: int, top_k: int, dtype):
    """Seat assignments choice-rank-major: all rank-0 choices take slots in
    token order before any rank-1 choice competes (GShard's policy —
    second choices absorb the drops, not first choices).

    Returns (dispatch [T,E,C], combine [T,E,C], kept_assignments scalar,
    first_choice_onehot [T,E])."""
    T, E = probs.shape
    if top_k == 1:
        top_p, top_i = jnp.max(probs, -1, keepdims=True), jnp.argmax(
            probs, -1, keepdims=True
        )
    else:
        top_p, top_i = jax.lax.top_k(probs, top_k)  # [T, k]
    # Renormalized gates over the chosen experts (top-1: the raw prob,
    # preserving Switch semantics where unchosen mass downweights output).
    gates = top_p if top_k == 1 else top_p / jnp.sum(
        top_p, -1, keepdims=True
    )

    dispatch = jnp.zeros((T, E, capacity), dtype)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    counts = jnp.zeros((E,), jnp.int32)  # seats taken so far per expert
    kept_assignments = 0.0
    for r in range(top_k):
        oh = jax.nn.one_hot(top_i[:, r], E, dtype=jnp.int32)  # [T, E]
        pos_te = counts[None, :] + jnp.cumsum(oh, axis=0) - oh  # 0-based
        pos = jnp.sum(pos_te * oh, axis=-1)  # [T]
        kept = pos < capacity
        oh_f = oh.astype(dtype)
        d_r = (
            oh_f[:, :, None]
            * jax.nn.one_hot(pos, capacity, dtype=dtype)[:, None, :]
            * kept[:, None, None].astype(dtype)
        )
        dispatch = dispatch + d_r
        combine = combine + d_r.astype(jnp.float32) * gates[
            :, r, None, None
        ].astype(jnp.float32)
        counts = counts + jnp.sum(oh * kept[:, None], axis=0)
        kept_assignments = kept_assignments + jnp.mean(
            kept.astype(jnp.float32)
        )
    first_oh = jax.nn.one_hot(top_i[:, 0], E, dtype=jnp.float32)
    return dispatch, combine, kept_assignments, first_oh


def moe_ffn_sharded(
    params: Dict[str, Any],
    x_local: jax.Array,
    capacity: Optional[int] = None,
    *,
    axis_name: str = "ep",
    top_k: int = 1,
    capacity_factor: float = 1.25,
):
    """Expert-parallel MoE with an EXPLICIT token->expert ``lax.all_to_all``
    — call INSIDE shard_map with tokens sharded ``P('ep', None)`` and
    expert weights sharded ``P('ep', ...)``.

    This is the ICI-efficient dispatch: each device exchanges only its
    tokens' expert slabs (O(T*D/ep) per link) where the GSPMD einsum path
    of :func:`moe_ffn` lowers to all-gather + all-reduce (O(T*D) per
    device). Capacity is GROUP-WISE (each token shard owns ``capacity``
    slots per expert — GShard's grouped dispatch), so results match
    :func:`moe_ffn` exactly whenever nothing is dropped, and degrade
    per-group rather than globally under pressure.

    Args:
      params: from :func:`moe_params`, with ``w_up``/``w_down`` leaves
        arriving as this device's ``[E_local, ...]`` shard and ``router``
        replicated.
      x_local: ``[T_local, d_model]`` token shard.

    Returns ``([T_local, d_model], aux)``; aux losses are psum-averaged
    over the axis (identical on every device).
    """
    groups = jax.lax.axis_size(axis_name)
    T_local, d_model = x_local.shape
    E_local = params["w_up"].shape[0]
    E = E_local * groups
    if capacity is None:
        capacity = int(math.ceil(capacity_factor * T_local * top_k / E))
    capacity = min(capacity, T_local)

    logits = x_local.astype(jnp.float32) @ params["router"].astype(
        jnp.float32
    )
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, kept_assignments, first_oh = _dispatch_combine(
        probs, capacity, top_k, x_local.dtype
    )

    # Local expert slabs for ALL experts, then the all-to-all routes slab
    # [g, e_loc] to the device owning experts e_loc (and brings back every
    # group's slab for OUR experts): [E,C,D] -> [G, E_loc, C, D].
    xe = jnp.einsum("tec,td->ecd", dispatch, x_local)
    xe = xe.reshape(groups, E_local, capacity, d_model)
    xe = jax.lax.all_to_all(
        xe, axis_name, split_axis=0, concat_axis=0, tiled=False
    )  # [G, E_local, C, D]: row g = group g's tokens for my experts

    h = jax.nn.gelu(
        jnp.einsum(
            "gecd,edh->gech", xe, params["w_up"].astype(x_local.dtype)
        )
    )
    ye = jnp.einsum(
        "gech,ehd->gecd", h, params["w_down"].astype(x_local.dtype)
    )
    # Reverse exchange: send group g its tokens' outputs back.
    ye = jax.lax.all_to_all(
        ye, axis_name, split_axis=0, concat_axis=0, tiled=False
    )  # [G, E_local, C, D] = my tokens' outputs from every expert shard
    ye = ye.reshape(E, capacity, d_model)
    y = jnp.einsum("tec,ecd->td", combine.astype(x_local.dtype), ye)

    frac_tokens = jax.lax.pmean(jnp.mean(first_oh, axis=0), axis_name)
    frac_probs = jax.lax.pmean(jnp.mean(probs, axis=0), axis_name)
    aux = {
        "load_balance_loss": E * jnp.sum(frac_tokens * frac_probs),
        "router_z_loss": jax.lax.pmean(
            jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2),
            axis_name,
        ),
        "drop_fraction": jax.lax.pmean(
            1.0 - kept_assignments / top_k, axis_name
        ),
    }
    return y, aux


_LANES = 128
_ROW_TILES = (256, 128)  # 512 is no faster on a v5e and its steps are coarser
_TILE_CAP = 1152  # three such tiles and an accumulator fit a v5e's VMEM


def _lane_tile(dim: int) -> Optional[int]:
    """The largest multiple of 128 that divides ``dim``, up to the cap."""
    return next(
        (t for t in range(min(dim, _TILE_CAP) // _LANES * _LANES, 0, -_LANES)
         if dim % t == 0),
        None,
    )


def _gmm_tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of one grouped product, from its own sizes: the kernels ask
    for them once a call, the two backward products with theirs."""
    return (
        next(t for t in _ROW_TILES if m % t == 0), _lane_tile(k),
        _lane_tile(n),
    )


def resolve_grouped(rows: int, d_in: int, d_out: int, dtype) -> str:
    """What :func:`moe_dropless` multiplies with, ``gmm`` or
    ``ragged_dot``, decided from what can be observed at trace time, the
    platform and the shapes (a test that has to steer it replaces this
    function; ``gmm_interpret`` then runs the kernels in the Pallas
    interpreter):

    - ``gmm`` on a TPU when the buffer's rows and both widths tile (rows a
      multiple of 128, widths of the lane width) and the operands are
      bfloat16 or float32: the Pallas grouped matmul that ships with jax
      (``jax.experimental.pallas.ops.tpu.megablox``). It walks the row
      tiles of its groups and no others, so its time goes by the rows it
      is given and not by which expert they went to;
    - ``ragged_dot`` otherwise: ``jax.lax.ragged_dot``, which every
      platform runs (on a v5e XLA's own expansion of it ran this repo's
      shapes at an eighth of the chip's peak, and its time moved by 6% with
      the same assignments dealt differently: PERF.md, PR 26).
    """
    if (
        jax.default_backend() == "tpu"
        and rows % _ROW_TILES[-1] == 0
        and d_in % _LANES == 0
        and d_out % _LANES == 0
        and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32)
    ):
        return "gmm"
    return "ragged_dot"


def _grouped_product(a, w, group_sizes, how: str):
    """``a[rows of group g] @ w[g]`` for every group; rows past the last
    group hold whatever the kernel left there."""
    if how == "ragged_dot":
        return jax.lax.ragged_dot(
            a, w, group_sizes, preferred_element_type=a.dtype
        )
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    return megablox.gmm(
        a, w, group_sizes, a.dtype, _gmm_tiling,
        interpret=how == "gmm_interpret",
    )


_SCORING = {
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


def moe_dropless(
    params: Dict[str, Any],
    x: jax.Array,
    *,
    top_k: int,
    held: Optional[Tuple[int, int]] = None,
    buffer_rows: Optional[int] = None,
    scoring: str = "softmax",
    select_bias: Optional[jax.Array] = None,
    gate_scale: float = 1.0,
):
    """Dropless top-``top_k`` MoE with gated experts. ``x``: [T, d_model].

    ``params``: ``router`` [d_model, E] (scores every expert, in float32),
    and the experts held here, ``w_gate``/``w_up`` [count, d_model, d_ff]
    and ``w_down`` [count, d_ff, d_model]: expert ``first + i`` of the
    router is row ``i``. ``held=(first, count)`` defaults to all ``E``.
    Without a ``w_gate`` the experts are :func:`moe_ffn`'s, plain GELU
    (``gelu(x @ w_up_e) @ w_down_e``), so its parameters run here as they
    are.

        p = softmax(x @ router);  S = top_k(p);  g_e = p_e / sum_S p
        y = sum over e in S that are held of
            g_e * (silu(x @ w_gate_e) * (x @ w_up_e)) @ w_down_e

    A token none of whose choices is held gets ``y = 0``.

    The router's rule is the call's: ``scoring`` is ``softmax`` (above) or
    ``sigmoid``, each expert scored by itself, ``p = sigmoid(x @ router)``.
    ``select_bias`` [E] moves the *choice* and nothing else: ``S =
    top_k(p + select_bias)``, the gates still ``p_e / sum_S p``. It enters
    under ``stop_gradient``, so its gradient is exactly zero and whoever
    balances the experts with it does so outside the loss (the
    auxiliary-loss-free rule of Wang et al. 2024; no such rule is built
    here). ``gate_scale`` multiplies the renormalised gates.

    Shapes are static. The gathered buffer has ``T * top_k`` rows, every
    assignment there is: the worst any routing can ask of the experts
    held. With a share of the experts that is several times what they are
    sent on average, so ``buffer_rows`` may state the size that usually
    does. The layer is then built twice, over ``buffer_rows`` rows and over
    the worst case, and a ``lax.cond`` runs the first where the assignments
    held fit it (gather and combine cost by the row). Nothing is dropped
    whichever runs; ``moe_spills`` is 1 where it was the second. The
    stated buffer is a budget of work as well as of rows: the grouped
    products multiply all of it, spare rows (zeros) included, so that a
    step costs the same whichever experts the tokens chose, as long as they
    fit; a chip of a group that meets at an all-reduce after every layer
    gains nothing by finishing a light layer early, and a step whose time
    goes by the routing cannot be compared from one batch to the next. The
    worst-case buffer multiplies only the rows in use. The two
    then rebuild the gathered rows and the experts' hidden activations in
    the backward pass and keep none, so the step's memory is the tokens'
    and not the buffer's: a layer that keeps its rows keeps room for the
    worst case's whichever buffer runs (at 8,192 tokens, top-8 and eight
    layers 19.7 GB compiled against 9.9 GB).

    The grouped product is :func:`resolve_grouped`'s choice.

    Returns ``(y [T, d_model], aux)``; ``aux`` holds counters (float32
    scalars): ``moe_assignments_held`` / ``moe_assignments_total``,
    ``moe_tokens_unserved``, ``moe_load_max`` / ``moe_load_mean``
    (assignments of the fullest held expert and their mean),
    ``moe_spills``, and ``moe_overflow``: the assignments held that the
    buffer that ran had no row for, counted by the branch that ran; 0, or
    the layer is wrong. And one array, ``moe_router_load`` [E] int32: the
    assignments the router sent to each of its ``E`` experts, held here or
    not.
    """
    T, _ = x.shape
    E = params["router"].shape[-1]
    first, count = (0, E) if held is None else held
    if params["w_up"].shape[0] != count or not 0 <= first <= E - count:
        raise ValueError(
            f"held={held!r} against {params['w_up'].shape[0]} expert "
            f"rows and a router over {E}"
        )
    if scoring not in _SCORING:
        raise ValueError(f"scoring={scoring!r}; have {sorted(_SCORING)}")
    worst = T * top_k
    bound = worst if buffer_rows is None else min(buffer_rows, worst)
    experts = {k: v for k, v in params.items() if k != "router"}

    with jax.named_scope("moolib.moe.route"):
        logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
        scores = _SCORING[scoring](logits)
        if select_bias is None:
            top_p, top_i = jax.lax.top_k(scores, top_k)
        else:
            _, top_i = jax.lax.top_k(
                scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
                top_k,
            )
            top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        gates = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)).reshape(-1)
        if gate_scale != 1.0:
            gates = gates * gate_scale
        # One key an assignment: its expert's row here, or `count` when the
        # expert lives elsewhere; a stable sort puts the held ones first,
        # in expert order, each expert's tokens in token order.
        local = top_i.reshape(-1) - first
        is_held = jnp.logical_and(local >= 0, local < count)
        key = jnp.where(is_held, local, count)
        order = jnp.argsort(key, stable=True)
        router_load = jnp.sum(
            top_i.reshape(-1)[:, None] == jnp.arange(E)[None, :], axis=0,
            dtype=jnp.int32,
        )
        load = router_load[first:first + count]
        held_total = jnp.sum(load)

    def over(rows: int):
        """The layer over a buffer of ``rows`` rows: ``y``, and how many
        of the assignments held it seated."""

        how = resolve_grouped(
            rows, x.shape[-1], experts["w_up"].shape[-1], x.dtype
        )

        def run(x, experts, gates):
            with jax.named_scope("moolib.moe.route"):
                ends = jnp.minimum(jnp.cumsum(load), rows)
                group_sizes = jnp.diff(ends, prepend=0)
                # Rows past the last group belong to no expert held here.
                used = (jnp.arange(rows) < ends[-1])[:, None]
                if rows < worst:
                    # A stated buffer is multiplied whole: its spare rows
                    # (zeros in, and cut from the result below) ride with
                    # the last expert, so the products' time is the
                    # buffer's, whatever the routing sent.
                    group_sizes = group_sizes.at[-1].add(rows - ends[-1])
                token = order[:rows] // top_k
                gate = gates[order[:rows]].astype(x.dtype)[:, None]
            with jax.named_scope("moolib.moe.gather"):
                xg = jnp.where(used, x[token], 0)  # [rows, d_model]
            with jax.named_scope("moolib.moe.experts"):
                def grouped(a, w):
                    # The product writes only the rows of its groups,
                    # forward and backward: what lies past them is not
                    # zero but whatever the buffer held (on the chip, NaN
                    # soon enough), and a zero on the other side of a
                    # later product does not cancel that. So every
                    # product's result is cut to the rows in use, and so
                    # (the select's transpose) is every cotangent on its
                    # way back in.
                    out = _grouped_product(
                        a, w.astype(a.dtype), group_sizes, how
                    )
                    return jnp.where(used, out, 0)

                h = grouped(xg, experts["w_up"])
                if "w_gate" in experts:
                    h = jax.nn.silu(grouped(xg, experts["w_gate"])) * h
                else:
                    h = jax.nn.gelu(h)
                ye = grouped(h, experts["w_down"])
            with jax.named_scope("moolib.moe.combine"):
                y = jnp.zeros_like(x).at[token].add(ye * gate)
            return y, ends[-1]

        return run if bound == worst else jax.checkpoint(run)

    if bound == worst:
        spills = jnp.zeros((), bool)
        y, seated = over(worst)(x, experts, gates)
    else:
        spills = held_total > bound
        y, seated = jax.lax.cond(
            spills, over(worst), over(bound), x, experts, gates
        )

    f32 = jnp.float32
    aux = {
        "moe_assignments_held": held_total.astype(f32),
        "moe_assignments_total": jnp.asarray(worst, f32),
        "moe_tokens_unserved": jnp.sum(
            ~jnp.any(is_held.reshape(T, top_k), axis=-1)
        ).astype(f32),
        "moe_load_max": jnp.max(load).astype(f32),
        "moe_load_mean": jnp.mean(load.astype(f32)),
        "moe_spills": spills.astype(f32),
        "moe_overflow": (held_total - seated).astype(f32),
        "moe_router_load": router_load,
    }
    return y, aux
