"""Sparse experts: one dropless mixture-of-experts layer.

The reference has no expert layer (SURVEY.md §2.5); this is TPU-first
scope. :func:`moe_dropless` drops no token at any imbalance and builds no
``[T, E, C]`` tensor. Assignments are sorted by expert, the tokens of the
experts **held here** (``held=(first, count)`` of the router's width) are
gathered in expert order, one grouped product a projection runs the gated
(SwiGLU) experts (on a TPU the Pallas grouped matmul that ships with jax,
elsewhere ``jax.lax.ragged_dot``: :func:`resolve_grouped`), and a
scatter-add combines by the renormalised top-k gates (or, for a router
that says so, by the chosen scores as they are); both move the rows in use
and no others (:func:`take_rows`, :func:`add_rows`). The scores are the
caller's (:func:`linear_scores` for a router of one matrix). With ``held`` a
strict share it is one chip's part of an expert-parallel layer (what the
absent experts would add is left out, and no code stands in for their
chips); with ``held=None`` it is the whole layer. Its buffer may be sized
for the usual routing, with the worst case's behind a ``lax.cond``.

The layer is pure (call under jit); ``models/lm.py`` holds its parameters.
The exchange of tokens between the chips of an ``ep`` axis is not written
yet (ROADMAP R2, R19).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["linear_scores", "moe_dropless", "resolve_grouped"]


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


# The rows one trip of the row walks moves, and the most bytes such a tile
# may hold: a v5e ran XLA's scatter-add of a tile of 7 MB or more some
# three times slower a row than one of 4.7 MB or less, and tiles of 256 to
# 1,024 rows under that size alike (PERF.md, Findings PR 48).
_WALK_TILE = 512
_WALK_TILE_BYTES = 4 << 20


def _walk_tile(rows: int, tokens: int, width: int, dtype) -> int:
    """The tile a layer's :func:`take_rows` and :func:`add_rows` walk its
    buffer of ``rows`` rows of ``width`` over ``tokens`` tokens in, from
    the shapes alone: ``_WALK_TILE`` where it divides the buffer, a tile is
    no more than ``_WALK_TILE_BYTES`` and the buffer has at least as many
    rows as there are tokens; else ``rows``: one walk of the whole buffer,
    no loop. A loop costs the step a pass or two over ``[tokens, width]``
    at its edges, whatever it skips, and saves the spare rows' movement: on
    a v5e buffers of 1.25 and 2.5 rows a token gained and buffers of half a
    row a token lost 1.5% of their step (PERF.md, Findings PR 48). Rows
    wider than the chip was read at keep XLA's whole-buffer passes."""
    tile_bytes = _WALK_TILE * width * jnp.dtype(dtype).itemsize
    if (rows % _WALK_TILE == 0 and rows > _WALK_TILE and rows >= tokens
            and tile_bytes <= _WALK_TILE_BYTES):
        return _WALK_TILE
    return rows


def _rows_walked(tile: int, n: jax.Array) -> jax.Array:
    """Rows a walk in tiles of ``tile`` moves when ``n`` are in use: the
    tiles that hold one, whole."""
    return (n + tile - 1) // tile * tile


def _walk(tile: int, rows: int, n: jax.Array, trip, carry):
    """``carry`` through ``trip(carry, start, keep)`` for every ``tile``
    rows of a ``rows``-row buffer that hold a row below ``n``, in order;
    ``start`` is the tile's first row and ``keep`` [tile, 1] says which of
    its rows are below ``n``. With ``tile`` the whole buffer it is one
    call; else the trip count is read on the device."""
    if tile == rows:
        return trip(carry, 0, (jnp.arange(rows) < n)[:, None])

    def body(i, carry):
        start = i * tile
        return trip(carry, start, (start + jnp.arange(tile) < n)[:, None])

    return jax.lax.fori_loop(0, _rows_walked(tile, n) // tile, body, carry)


def _take(scope: str, x, token, n, tile: int):
    rows, width = token.shape[0], x.shape[1]

    def trip(buffer, start, keep):
        tokens = jax.lax.dynamic_slice(token, (start,), (tile,))
        return jax.lax.dynamic_update_slice(
            buffer, jnp.where(keep, x[tokens], 0), (start, 0)
        )

    with jax.named_scope(scope):
        return _walk(tile, rows, n, trip, jnp.zeros((rows, width), x.dtype))


def _add(scope: str, v, token, n, T: int, tile: int):
    rows, width = v.shape

    def trip(total, start, keep):
        tokens = jax.lax.dynamic_slice(token, (start,), (tile,))
        part = jax.lax.dynamic_slice(v, (start, 0), (tile, width))
        return total.at[tokens].add(jnp.where(keep, part, 0))

    with jax.named_scope(scope):
        return _walk(tile, rows, n, trip, jnp.zeros((T, width), v.dtype))


# The two row movers of the layer, each the other's transpose in its array
# argument, so each one's backward rule is the other (under the scope of the
# forward it belongs to: a custom rule's backward inherits none). ``token``
# and ``n`` are integers: no gradient, and the only residuals. ``tile``
# (static) divides the buffer's rows: :func:`_walk_tile`'s answer.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _take_rows(x, token, n, T, tile):
    return _take("moolib.moe.gather", x, token, n, tile)


def _take_rows_fwd(x, token, n, T, tile):
    return _take("moolib.moe.gather", x, token, n, tile), (token, n)


def _take_rows_bwd(T, tile, residuals, g):
    return _add("moolib.moe.gather", g, *residuals, T, tile), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def add_rows(v, token, n, T, tile):
    """``v`` [rows, d] summed into [T, d]: row ``r`` into row ``token[r]``,
    over ``r < n`` only. It walks the tiles of ``tile`` rows that hold such
    a row and no others; its transpose is :func:`take_rows`."""
    return _add("moolib.moe.combine", v, token, n, T, tile)


def _add_rows_fwd(v, token, n, T, tile):
    return _add("moolib.moe.combine", v, token, n, T, tile), (token, n)


def _add_rows_bwd(T, tile, residuals, g):
    return _take("moolib.moe.combine", g, *residuals, tile), None, None


add_rows.defvjp(_add_rows_fwd, _add_rows_bwd)


def take_rows(x, token, n, tile):
    """``x`` [T, d] gathered into [rows, d]: row ``r < n`` is
    ``x[token[r]]``, every row from ``n`` on is zero. It walks the tiles of
    ``tile`` rows that hold a row below ``n`` and no others; its transpose
    is :func:`add_rows`."""
    return _take_rows(x, token, n, x.shape[0], tile)


_SCORING = {
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


def linear_scores(x: jax.Array, router: jax.Array,
                  scoring: str = "softmax") -> jax.Array:
    """The scores of a router that is one matrix: ``x`` [T, d_model] and
    ``router`` [d_model, E] give ``p`` [T, E] in float32, ``softmax(x @
    router)`` or, with ``scoring="sigmoid"``, each expert scored by itself,
    ``sigmoid(x @ router)``."""
    if scoring not in _SCORING:
        raise ValueError(f"scoring={scoring!r}; have {sorted(_SCORING)}")
    with jax.named_scope("moolib.moe.route"):
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        return _SCORING[scoring](logits)


def moe_dropless(
    params: Dict[str, Any],
    x: jax.Array,
    scores: jax.Array,
    *,
    top_k: int,
    held: Optional[Tuple[int, int]] = None,
    buffer_rows: Optional[int] = None,
    select_bias: Optional[jax.Array] = None,
    gate_scale: float = 1.0,
    skip_choices: int = 0,
    renormalize: bool = True,
):
    """Dropless top-``top_k`` MoE with gated experts. ``x``: [T, d_model].

    ``scores`` [T, E'] float32 are the router's, ``p`` below: the caller
    scores, by one matrix (:func:`linear_scores`) or by whatever its router
    is (an MLP, a state carried through the depth). ``params``: the experts
    held here, ``w_gate``/``w_up`` [count, d_model, d_ff] and ``w_down``
    [count, d_ff, d_model]: expert ``first + i`` of the router is row
    ``i``. ``held=(first, count)`` defaults to all ``E``.

        S = top_k(p);  g_e = p_e / sum_S p
        y = sum over e in S that are held of
            g_e * (silu(x @ w_gate_e) * (x @ w_up_e)) @ w_down_e

    A token none of whose choices is held gets ``y = 0``.

    ``select_bias`` [E'] moves the *choice* and nothing else: ``S =
    top_k(p + select_bias)``, the gates still ``p_e / sum_S p``. It enters
    under ``stop_gradient``, so its gradient is exactly zero and whoever
    balances the experts with it does so outside the loss (the
    auxiliary-loss-free rule of Wang et al. 2024; no such rule is built
    here). ``gate_scale`` multiplies the gates.

    The last ``skip_choices`` columns of ``scores`` are choices that are
    **no expert** (``E = E' - skip_choices``): a token whose choice falls
    there is served by nobody, here or elsewhere. Such an assignment is
    sorted with those of the experts held elsewhere, past the rows in use,
    and counted apart (``moe_tokens_skipped``); ``moe_tokens_unserved``
    counts it too.

    ``renormalize=False`` leaves the gates as the chosen scores, ``g_e =
    p_e``. With ``top_k`` 1 the renormalised gate is ``p_e / p_e``, 1
    whatever the router says: the output then does not depend on the
    scores but through the choice, which has no derivative, and **the
    router's gradient is zero** (in floating point, the rounding of ``1 /
    p - p / p^2``). A top-1 router learns through its unrenormalised gate
    or not at all, so ``top_k=1`` with ``renormalize=True`` is refused.

    Shapes are static. The gathered buffer has ``T * top_k`` rows, every
    assignment there is: the worst any routing can ask of the experts
    held. With a share of the experts that is several times what they are
    sent on average, so ``buffer_rows`` may state the size that usually
    does. The layer is then built twice, over ``buffer_rows`` rows and over
    the worst case, and a ``lax.cond`` runs the first where the assignments
    held fit it. Nothing is dropped whichever runs; ``moe_spills`` is 1
    where it was the second. Gather and combine cost by the rows in use,
    whichever buffer runs, where the stated buffer has a row a token or
    more (:func:`_walk_tile`): :func:`take_rows` and :func:`add_rows` walk
    it in tiles of ``R`` rows and stop after the tile that holds the last
    assignment seated, so the spare rows are written as zeros once and
    never moved; a smaller buffer is moved whole, as it saves less than
    the loops cost. The products still cost by the buffer. The
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
    ``moe_spills``, ``moe_overflow``: the assignments held that the
    buffer that ran had no row for, counted by the branch that ran; 0, or
    the layer is wrong, and ``moe_rows_moved``: the rows one gather of
    this layer walked, ``ceil(seated / R) * R`` (beside
    ``moe_assignments_held``: near it where the walk stops early, the
    buffer's rows where the buffer is one walk). With ``skip_choices``,
    ``moe_tokens_skipped``;
    with ``renormalize=False``, ``moe_gate_mean``, the mean chosen score
    (``1 / E'`` at a flat softmax router, 1 at a collapsed one). And one
    array, ``moe_router_load`` [E'] int32: the assignments the router sent
    to each of its choices, an expert held here or not, or none.
    """
    T, _ = x.shape
    width = scores.shape[-1]
    E = width - skip_choices
    first, count = (0, E) if held is None else held
    if params["w_up"].shape[0] != count or not 0 <= first <= E - count:
        raise ValueError(
            f"held={held!r} against {params['w_up'].shape[0]} expert "
            f"rows and a router over {E}"
        )
    if renormalize and top_k == 1:
        raise ValueError(
            "top_k=1 with renormalize=True: the gate is p / p, 1 whatever "
            "the router says, and the router's gradient is zero"
        )
    worst = T * top_k
    bound = worst if buffer_rows is None else min(buffer_rows, worst)

    with jax.named_scope("moolib.moe.route"):
        if select_bias is None:
            top_p, top_i = jax.lax.top_k(scores, top_k)
        else:
            _, top_i = jax.lax.top_k(
                scores + jax.lax.stop_gradient(select_bias.astype(jnp.float32)),
                top_k,
            )
            top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        gates = top_p
        if renormalize:
            gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gates = gates.reshape(-1)
        if gate_scale != 1.0:
            gates = gates * gate_scale
        # One key an assignment: its expert's row here, or `count` when the
        # expert lives elsewhere (or the choice is no expert: its id is
        # past every expert's); a stable sort puts the held ones first,
        # in expert order, each expert's tokens in token order.
        local = top_i.reshape(-1) - first
        is_held = jnp.logical_and(local >= 0, local < count)
        key = jnp.where(is_held, local, count)
        order = jnp.argsort(key, stable=True)
        router_load = jnp.sum(
            top_i.reshape(-1)[:, None] == jnp.arange(width)[None, :], axis=0,
            dtype=jnp.int32,
        )
        load = router_load[first:first + count]
        held_total = jnp.sum(load)

    # One answer a layer, its stated buffer's: the worst case's buffer is
    # walked as that one is, where the tile divides it.
    walk = _walk_tile(bound, T, x.shape[1], x.dtype)

    def over(rows: int):
        """The layer over a buffer of ``rows`` rows: ``y``, how many of
        the assignments held it seated, and the rows its gather walked."""

        tile = walk if walk < bound and rows % walk == 0 else rows

        how = resolve_grouped(
            rows, x.shape[-1], params["w_up"].shape[-1], x.dtype
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
            xg = take_rows(x, token, ends[-1], tile)  # [rows, d_model]
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
                h = jax.nn.silu(grouped(xg, experts["w_gate"])) * h
                ye = grouped(h, experts["w_down"])
            with jax.named_scope("moolib.moe.combine"):
                ye = ye * gate
            y = add_rows(ye, token, ends[-1], T, tile)
            return y, ends[-1], _rows_walked(tile, ends[-1])

        return run if bound == worst else jax.checkpoint(run)

    if bound == worst:
        spills = jnp.zeros((), bool)
        y, seated, walked = over(worst)(x, params, gates)
    else:
        spills = held_total > bound
        y, seated, walked = jax.lax.cond(
            spills, over(worst), over(bound), x, params, gates
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
        "moe_rows_moved": walked.astype(f32),
        "moe_router_load": router_load,
    }
    if skip_choices:
        aux["moe_tokens_skipped"] = jnp.sum(top_i >= E).astype(f32)
    if not renormalize:
        aux["moe_gate_mean"] = jnp.mean(top_p)
    return y, aux
