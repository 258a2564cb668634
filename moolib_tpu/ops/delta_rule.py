"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692), in chunks, with episode boundaries and a carried state.

One head, a state ``S`` in ``R^{Dk x Dv}``, a position ``t``:

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = Dk^-1/2 * S_t^T q_t

``g_t <= 0`` a channel of the key, ``beta_t`` in (0, 2) (above 1 the
transition has a negative eigenvalue). At an episode's first position
``S_{t-1}`` is zero; at the call's first position it is the state handed
in, and the call returns ``S`` after its last.

**The chunked form.** Write ``u_t = beta_t (v_t - (diag(exp(g_t))
S_{t-1})^T k_t)``; then ``S_t = diag(exp(g_t)) S_{t-1} + k_t u_t^T``, and
inside a chunk of ``C`` positions that enters with ``S_0``, with ``G_r`` the
sum of ``g`` over the chunk's positions up to ``r``:

    A_ri = sum_c k_rc k_ic exp(G_rc - G_ic)   (i < r)     [C, C]
    B_ri = sum_c q_rc k_ic exp(G_rc - G_ic)   (i <= r)    [C, C]
    (I + diag(beta) A) U = diag(beta) (V - (exp(G) * K) S_0)
    O   = Dk^-1/2 ((exp(G) * Q) S_0 + B U)
    S_C = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

``U`` is linear in ``S_0``: with ``Tinv`` the inverse of the unit
triangular matrix, ``W_v = Tinv diag(beta) V`` and ``W_k = Tinv diag(beta)
(exp(G) * K)``, ``U = W_v - W_k S_0``. So every chunk's ``A``, ``B``,
``Tinv``, ``W`` and the four products ``B W_v``, ``exp(G) Q - B W_k``,
``Kt^T W_v`` and ``diag(exp(G_C)) - Kt^T W_k`` (``Kt = K * exp(G_C - G)``)
are computed for all chunks at once, as matrix products; what is
sequential is ``S' = P + M S``, one ``[Dk, Dk] x [Dk, Dv]`` product a
chunk and head, ``T / C`` steps; the outputs are then one more batched
product with the states the scan collected. No loop runs over positions.

**No positive exponent.** ``exp(G_r) exp(-G_i)`` overflows float32 where
the gates are strong (64 positions at ``g`` = -10: ``exp(640)``), so the
pair factors are never formed that way. A chunk is cut into sub-blocks of
16 positions. A pair in one sub-block takes ``exp(G_r - G_i)`` directly,
masked to ``i <= r`` *before* the exponential; a pair whose key lies in an
earlier sub-block takes ``exp(G_r - E_a) exp(E_a - G_i)`` with ``E_a`` the
sum up to the query's sub-block, both exponents sums of ``g`` and so not
positive. ``exp(G)``, ``exp(G_C - G)`` are of that kind already.

**Boundaries.** ``seg`` [B, T] never decreases; the state handed in is of
episode 0, so a first id of 0 continues it and any other drops it
(``segment_ids_from_done``'s ids: the running count of ``done``). Inside a
chunk, pairs of different episodes are masked out of ``A`` and ``B``;
``S_0`` reaches the positions of the episode that was running before the
chunk's first position only; of the chunk's updates those of its last
position's episode are handed on. The sums ``G`` may run across a
boundary: only differences inside one episode are ever used.

The inverse of ``I + diag(beta) A`` is by forward substitution on the
diagonal blocks of 16 (16 unrolled row steps over all blocks of all
chunks at once) and two block merges; the backward pass is the
differentiation of all this, whose transposed scan runs the other way.
Everything is float32, the small products at ``HIGHEST`` precision: the
rule feeds its own output back through ``T / C`` products, and the whole
of it is a few percent of a decoder block's FLOPs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..telemetry import global_telemetry

__all__ = ["CHUNK", "chunk_of", "gated_delta_rule", "log_decay_min"]

CHUNK = 64  # positions a chunk: the scan over chunks has T / CHUNK steps
SUB = 16  # positions a sub-block: pairs inside one take their decay directly
PATH = "chunked"  # what recurrent_mix_calls_traced_total{path=} counts

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec: str, *operands):
    return jnp.einsum(spec, *operands, precision=_HIGHEST)


def chunk_of(T: int) -> int:
    """The chunk a call of ``T`` positions runs at: ``CHUNK``, or for a
    shorter call its length rounded up to whole sub-blocks."""
    return CHUNK if T >= CHUNK else -(-T // SUB) * SUB


def _pair_products(x, k, G, g):
    """``sum_c x_rc k_ic exp(G_rc - G_ic)`` for every pair ``i <= r`` of a
    chunk (what lies above the diagonal is left 0), with no positive
    exponent. ``x``, ``k``, ``G``, ``g`` [..., C, Dk] -> [..., C, C]."""
    *lead, C, Dk = k.shape
    s = C // SUB
    blocks = lambda t: t.reshape(*lead, s, SUB, Dk)  # noqa: E731
    xb, kb, Gb, gb = blocks(x), blocks(k), blocks(G), blocks(g)
    # inside a sub-block: the decay of each pair, masked before the exp
    lower = jnp.tril(jnp.ones((SUB, SUB), bool))[..., None]
    decay = jnp.exp(jnp.where(
        lower, Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf
    ))
    inside = jnp.einsum("...rc,...ic,...ric->...ri", xb, kb, decay)
    # a key of an earlier sub-block: through the sum up to the query's
    start = Gb[..., 0, :] - gb[..., 0, :]  # [..., s, Dk]
    left = xb * jnp.exp(Gb - start[..., None, :])
    earlier = (jnp.arange(s)[:, None] > jnp.arange(s)[None, :])[
        ..., None, None
    ]
    right = kb[..., None, :, :, :] * jnp.exp(jnp.where(
        earlier,
        start[..., :, None, None, :] - Gb[..., None, :, :, :], -jnp.inf,
    ))  # [..., a, b, i, Dk]
    pairs = _mm("...arc,...abic->...arbi", left, right)
    pairs = pairs + inside[..., :, :, None, :] * jnp.eye(s)[:, None, :, None]
    return pairs.reshape(*lead, C, C)


def _unit_lower_inverse(t):
    """The inverse of ``t`` [..., m, m], unit lower triangular, ``m`` a
    multiple of ``SUB``: forward substitution row by row on a block of
    ``SUB`` (unrolled: no loop in the program), and for a larger one the
    two halves' inverses and ``-inv(t22) t21 inv(t11)`` below them."""
    m = t.shape[-1]
    if m == SUB:
        rows = [jnp.zeros(t.shape[:-2] + (m,), t.dtype).at[..., 0].set(1.0)]
        for r in range(1, m):
            unit = jnp.zeros(t.shape[:-2] + (m,), t.dtype).at[..., r].set(1.0)
            rows.append(unit - _mm(
                "...j,...jm->...m", t[..., r, :r], jnp.stack(rows, axis=-2)
            ))
        return jnp.stack(rows, axis=-2)
    h = (m // SUB // 2) * SUB
    inv1 = _unit_lower_inverse(t[..., :h, :h])
    inv2 = _unit_lower_inverse(t[..., h:, h:])
    below = -_mm("...ij,...jk,...kl->...il", inv2, t[..., h:, :h], inv1)
    top = jnp.concatenate(
        [inv1, jnp.zeros(t.shape[:-2] + (h, m - h), t.dtype)], axis=-1
    )
    return jnp.concatenate(
        [top, jnp.concatenate([below, inv2], axis=-1)], axis=-2
    )


def log_decay_min(g):
    """The most negative sum of ``g`` [B, H, T, Dk] over one chunk, any
    channel: what ``exp`` of its negative would have had to hold."""
    B, H, T, Dk = g.shape
    C = chunk_of(T)
    g = jnp.pad(g.astype(jnp.float32), ((0, 0),) * 2 + ((0, -T % C), (0, 0)))
    return jnp.min(jnp.sum(g.reshape(B, H, -1, C, Dk), axis=3))


def gated_delta_rule(q, k, v, g, beta, seg, state):
    """The rule of the module's docstring over a whole call. ``q``, ``k``,
    ``g`` [B, H, T, Dk], ``v`` [B, H, T, Dv], ``beta`` [B, H, T], ``seg``
    [B, T] episode ids, ``state`` [B, H, Dk, Dv] float32 of episode 0.
    Returns ``o`` [B, H, T, Dv] in ``v``'s dtype and the state after the
    last position, float32. Counted where traced in
    ``recurrent_mix_calls_traced_total{path=}``."""
    global_telemetry().registry.counter(
        "recurrent_mix_calls_traced_total", path=PATH
    ).inc()
    B, H, T, Dk = q.shape
    Dv = v.shape[-1]
    C = chunk_of(T)
    pad = -T % C
    n = (T + pad) // C
    f32 = jnp.float32

    def chunks(x):  # [B, H, T, ...] -> [B, H, n, C, ...], zeros behind T
        x = jnp.pad(
            x.astype(f32), ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3)
        )
        return x.reshape(B, H, n, C, *x.shape[3:])

    # padding: k = 0, beta = 0, g = 0 leave the state as it is
    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta)
    seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    before = jnp.pad(seg[:, :-1], ((0, 0), (1, 0)))  # the state's episode: 0
    segc = seg.reshape(B, 1, n, C)
    # the positions the entering state reaches, the pairs of one episode,
    # the positions whose update the chunk hands on
    carry = (segc == before.reshape(B, 1, n, C)[..., :1]).astype(f32)
    same = segc[..., :, None] == segc[..., None, :]
    handed = (segc == segc[..., -1:]).astype(f32)

    G = jnp.cumsum(g, axis=3)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    A = jnp.where(strict & same, _pair_products(k, k, G, g), 0.0)
    Bm = jnp.where(
        (strict | jnp.eye(C, dtype=bool)) & same,
        _pair_products(q, k, G, g), 0.0,
    )
    Tinv = _unit_lower_inverse(jnp.eye(C, dtype=f32) + beta[..., None] * A)
    reach = (carry[..., None] * jnp.exp(G))  # exp(G) where S_0 reaches
    W = _mm(
        "...ri,...id->...rd", Tinv,
        beta[..., None] * jnp.concatenate([v, reach * k], axis=-1),
    )
    W_v, W_k = W[..., :Dv], W[..., Dv:]
    Kt = k * jnp.exp(G[..., -1:, :] - G) * handed[..., None]
    keep = reach[..., -1, :]  # [B, H, n, Dk]: diag(exp(G_C)), or 0
    O0 = _mm("...ri,...id->...rd", Bm, W_v)
    Qeff = reach * q - _mm("...ri,...ic->...rc", Bm, W_k)
    P = _mm("...ic,...id->...cd", Kt, W_v)
    M = keep[..., None] * jnp.eye(Dk, dtype=f32) - _mm(
        "...ic,...ie->...ce", Kt, W_k
    )

    def step(S, xs):
        P_n, M_n = xs
        return P_n + _mm("bhce,bhed->bhcd", M_n, S), S

    state, entering = jax.lax.scan(
        step, state.astype(f32),
        (jnp.moveaxis(P, 2, 0), jnp.moveaxis(M, 2, 0)),
    )
    o = Dk ** -0.5 * (O0 + _mm(
        "bhnrc,nbhcd->bhnrd", Qeff, entering
    ))
    return o.reshape(B, H, n * C, Dv)[:, :, :T].astype(v.dtype), state
