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
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["moe_params", "moe_ffn", "moe_ffn_sharded"]


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
