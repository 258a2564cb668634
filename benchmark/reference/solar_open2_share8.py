"""Plain reference of ``solar_open2_share8``: one chip's share of one
period of Solar-Open2-250B (upstage, ``config.json``, ``model_type``
solar_open2) as a token-level actor-critic policy, float32 ``jax.numpy``
from the equations. Imports nothing of the program.

x is [tokens, 4096]; one column of the batch is one packed sequence.

    rms(x) = x / sqrt(mean(x^2) + 1e-5) * w
    block:  h = x + Mixer(rms1(x));  out = h + Moe(rms2(h))

    softmax mixer (layer 0; no position encoding at all):
      q, k, v = z W_q, z W_k, z W_v  as heads of 128 (H query heads on one
      key/value head)
      key j visible to query i iff j <= i, both in one episode segment
        (segment = running count of `done`)
      a = softmax(q . k / sqrt(128)) v
      out = (concat(heads) * sigmoid(z W_g)) W_o

    delta-rule mixer (layers 1-3; H heads of 128, a state S [128, 128] a
    head, carried), position by position:
      pre_t = [z_t W_q | z_t W_k | z_t W_v]                   (3 H 128)
      c_t   = sum_{i=0..3} w_i * pre_{t-3+i}     a channel; a row of an
              earlier episode reads as zero
      [q | k | v]_t = silu(c_t) as heads;  q, k = x / sqrt(sum x^2 + 1e-6)
      g_t = -exp(A_h) * softplus((z_t W_f1 W_f2)_h + dt_bias_h)  in R^128
      beta_t = 2 sigmoid(z_t W_b)_h
      S <- diag(exp(g_t)) S              (S = 0 first, at an episode's
      S <- S + beta_t k_t (v_t - S^T k_t)^T       first position)
      o_t = S^T q_t / sqrt(128)
      out_t = [rms_head(o_t) * sigmoid((z_t W_g1 W_g2)_h)] W_o
    with rms_head over a head's 128 and one gain [128] for all heads.
    Before the call's first position stand ``core_state``'s S and three
    rows ``pre``, of the episode the call continues unless ``done[0]``;
    after its last the same are handed on (rows of an earlier episode than
    the last position's as zeros).

    sparse MLP (every layer):  s = sigmoid(z Wr) over all 320
                               S = the 8 largest of s + b  (b: selection bias)
                               g_e = s_e / sum_S s   (times scale 1)
                               out = sum over e in S held here of
                                     g_e * Expert_e(z)  +  Shared(z)
                               every expert and the shared one SwiGLU of 1280

    logits = rms_f(x) Whead;  baseline = rms_f(x) wv + b

The share and the depth are read off the parameter tree: a block whose
mixer has an ``A_log`` is a delta-rule block; a block whose leaves carry
one more leading axis is that many identical blocks, run as a scan (its
state's leaves then carry the blocks on their second axis); the experts
held are router ids ``first_expert`` .. + the rows of ``w_gate``; the heads
and the vocabulary are those held. What the absent experts and heads would
add is left out, as in the program. ``b`` is a constant of the
optimisation (``stop_gradient``).

Blocks, the rows of the score matrix, stretches of the recurrence and the
head with its loss are computed a block at a time and rebuilt in the
backward pass, so that no [H, T, T], no [T, 128, 128] and no [T,
vocabulary] array is ever held. ``cast`` rounds both operands of every
matrix product of the projections, the scores, the experts and the head
(identity for the reference proper; see ``lib/reference_train.py``); the
recurrence itself is float32 on either side.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference_train import vtrace_targets

# What the parameter shapes do not say: the published settings.
PUBLISHED = {
    "head_dim": 128,
    "top_k": 8,
    "routed_scaling_factor": 1.0,
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-5,
    "l2_eps": 1e-6,
    "beta_scale": 2.0,  # kda_allow_neg_eigval
    "query_rows": 256,  # rows of the score matrix computed at a time
    "scan_rows": 64,  # positions of the recurrence rebuilt at a time
    "head_rows": 1024,  # positions of the head's logits computed at a time
}


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def dot(a, w, cast):
    return cast(a) @ cast(w)


def softmax_mixer(z, p, seg, spec, cast):
    T, D = z.shape[0], spec["head_dim"]
    q = dot(z, p["q"]["kernel"], cast).reshape(T, -1, D)
    k = dot(z, p["k"]["kernel"], cast).reshape(T, -1, D)
    v = dot(z, p["v"]["kernel"], cast).reshape(T, -1, D)
    group = q.shape[1] // k.shape[1]  # query heads a key/value head
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    rows = min(spec["query_rows"], T)
    assert T % rows == 0, (T, rows)
    j = jnp.arange(T)

    @jax.checkpoint
    def block(start):
        i = start + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        s = jnp.einsum("ihd,jhd->hij", cast(qb), cast(k)) / math.sqrt(D)
        seen = (j[None, :] <= i[:, None]) & (
            jax.lax.dynamic_slice_in_dim(seg, start, rows)[:, None]
            == seg[None, :]
        )
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", cast(w), cast(v))

    a = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, -1)
    a = a * jax.nn.sigmoid(dot(z, p["gate"]["kernel"], cast))
    return dot(a, p["o"]["kernel"], cast)


def delta_mixer(z, p, seg, state, spec, cast):
    """``state``: ``(S [H, 128, 128], rows [3, 3 H 128])``. Returns the
    mixer's output [T, d] and the state after the last position."""
    T, D = z.shape[0], spec["head_dim"]
    S, tail = state
    H = S.shape[0]
    pre = jnp.concatenate(
        [dot(z, p[n]["kernel"], cast) for n in ("q", "k", "v")], axis=-1
    )
    taps = jnp.concatenate(
        [p["conv_q"], p["conv_k"], p["conv_v"]], axis=-1
    )  # [4, 3 H D]: tap 3 on the position itself
    decay = dot(
        dot(z, p["f_a"]["kernel"], cast), p["f_b"]["kernel"], cast
    ) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        decay.reshape(T, H, D)
    )
    beta = spec["beta_scale"] * jax.nn.sigmoid(dot(z, p["b"]["kernel"], cast))

    def l2norm(x):
        return x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + spec["l2_eps"]
        )

    def position(carry, xs):
        S, tail, episode = carry
        pre_t, g_t, beta_t, seg_t = xs
        first = seg_t != episode  # an episode's first position
        S = jnp.where(first, 0.0, S)
        tail = jnp.where(first, 0.0, tail)
        mixed = jnp.sum(taps[:-1] * tail, axis=0) + taps[-1] * pre_t
        q, k, v = jnp.split(jax.nn.silu(mixed).reshape(3 * H, D), 3)
        q, k = l2norm(q), l2norm(k)
        S = jnp.exp(g_t)[:, :, None] * S
        read = jnp.sum(S * k[:, :, None], axis=1)  # S^T k  [H, D]
        S = S + beta_t[:, None, None] * k[:, :, None] * (v - read)[:, None, :]
        o = jnp.sum(S * q[:, :, None], axis=1) / math.sqrt(D)
        tail = jnp.concatenate([tail[1:], pre_t[None]])
        return (S, tail, seg_t), o

    rows = min(spec["scan_rows"], T)
    assert T % rows == 0, (T, rows)

    @jax.checkpoint
    def stretch(carry, xs):
        return jax.lax.scan(position, carry, xs)

    # the state handed in is of episode 0: seg[0] is 1 where done[0]
    (S, tail, _), o = jax.lax.scan(
        stretch, (S, tail, jnp.zeros((), seg.dtype)),
        tuple(
            x.reshape(T // rows, rows, *x.shape[1:])
            for x in (pre, g, beta, seg)
        ),
    )
    o = rms(o.reshape(T, H, D), p["o_norm"]["scale"], spec["eps"])
    gate = dot(dot(z, p["g_a"]["kernel"], cast), p["g_b"]["kernel"], cast)
    o = o.reshape(T, H * D) * jax.nn.sigmoid(gate)
    return dot(o, p["o"]["kernel"], cast), (S, tail)


def gated(z, p, cast):
    """A SwiGLU MLP held as three dense layers."""
    hidden = jax.nn.silu(dot(z, p["gate"]["kernel"], cast)) * dot(
        z, p["up"]["kernel"], cast
    )
    return dot(hidden, p["down"]["kernel"], cast)


def route(z, p, spec, cast):
    """The experts chosen [T, k] and their gates [T, k]."""
    scores = jax.nn.sigmoid(dot(z, p["router"], cast))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["e_score_correction_bias"]),
        spec["top_k"],
    )
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, gates * spec["routed_scaling_factor"]


def experts(z, p, spec, cast):
    """The routed experts held here, and the shared expert."""
    chosen, gates = route(z, p, spec, cast)

    def one_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        # this expert's gate a token: 0 where the token did not choose it
        g = jnp.sum(
            jnp.where(chosen == spec["first_expert"] + e, gates, 0.0), axis=-1
        )
        hidden = jax.nn.silu(dot(z, w_gate, cast)) * dot(z, w_up, cast)
        return y + g[:, None] * dot(hidden, w_down, cast), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]),
    )
    return y + gated(z, p["shared"], cast)


def block(x, bp, seg, state, spec, cast):
    """One block; ``state`` is ``()`` for a softmax block and comes back
    so."""
    eps = spec["eps"]
    z = rms(x, bp["norm1"]["scale"], eps)
    if "A_log" in bp["attn"]:
        y, state = delta_mixer(z, bp["attn"], seg, state, spec, cast)
    else:
        y = softmax_mixer(z, bp["attn"], seg, spec, cast)
    h = x + y
    return h + experts(rms(h, bp["norm2"]["scale"], eps), bp["moe"], spec,
                       cast), state


def blocks(x, bp, seg, state, spec, cast):
    """One block, or as many as its leaves' leading axis says, each
    rebuilt in the backward pass; a stack's state has the blocks on its
    leaves' leading axis here (one column's)."""
    one = jax.checkpoint(
        lambda x, bp, state: block(x, bp, seg, state, spec, cast)
    )
    if bp["norm1"]["scale"].ndim == 1:
        return one(x, bp, state)
    return jax.lax.scan(
        lambda x, xs: one(x, *xs), x, (bp, state)
    )


def trunk(p, tokens, seg, core_state, spec, cast):
    """tokens [T] -> the last block's output [T, d] and the state handed
    on, flat as ``core_state`` came (one column's: no batch axis)."""
    x = p["embed"]["embedding"][tokens]
    states, handed_on = list(core_state), []
    for i in range(sum(1 for name in p if name.startswith("block_"))):
        bp, state = p[f"block_{i}"], ()
        if "A_log" in bp["attn"]:
            state, states = tuple(states[:2]), states[2:]
        x, state = blocks(x, bp, seg, state, spec, cast)
        handed_on += state
    return x, tuple(handed_on)


def by_rows(fn, rows, *arrays):
    """``fn`` over blocks of ``rows`` leading rows, each rebuilt in the
    backward pass; the blocks' results stacked."""
    T = arrays[0].shape[0]
    rows = min(rows, T)
    assert T % rows == 0, (T, rows)
    return jax.lax.map(
        lambda xs: jax.checkpoint(fn)(*xs),
        tuple(a.reshape(T // rows, rows, *a.shape[1:]) for a in arrays),
    )


def column_state(core_state, c):
    return tuple(s[c] for s in core_state)


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's sums: what the losses are means of."""
    tokens = chunk["obs"][:, c].astype(jnp.int32)
    seg = jnp.cumsum(chunk["done"][:, c].astype(jnp.int32))
    T1 = tokens.shape[0]
    h, _ = trunk(
        p, tokens, seg, column_state(chunk["core_state"], c), spec, cast
    )
    x = rms(h, p["final_norm"]["scale"], spec["eps"])
    baseline = dot(x, p["baseline"]["kernel"], cast)[:, 0] + p["baseline"][
        "bias"
    ][0]
    head = p["head"]["kernel"]
    # the policy's T rows, padded by the bootstrap row (unused) so that
    # the rows split into blocks
    actions = jnp.concatenate([chunk["actions"][:, c], jnp.zeros(1, jnp.int32)])
    behavior = jnp.concatenate(
        [chunk["behavior_logits"][:, c],
         jnp.zeros((1, head.shape[-1]), jnp.float32)]
    )

    def policy_rows(x, actions, behavior):
        logp = jax.nn.log_softmax(dot(x, head, cast), axis=-1)
        take = lambda lp: jnp.take_along_axis(  # noqa: E731
            lp, actions[:, None], axis=-1
        )[:, 0]
        return (take(logp), take(jax.nn.log_softmax(behavior, axis=-1)),
                -jnp.sum(jnp.exp(logp) * logp, axis=-1))

    target_lp, behavior_lp, entropy = (
        t.reshape(T1)[:-1]
        for t in by_rows(policy_rows, spec["head_rows"], x, actions, behavior)
    )
    return {
        "target_lp": target_lp, "behavior_lp": behavior_lp,
        "entropy": jnp.sum(entropy), "baseline": baseline,
    }


def make_loss(spec):
    def loss_fn(params, batch, loss, cast):
        """The step's total loss, the IMPALA loss of
        ``lib/reference_train.py`` (means over T x B), and, for
        ``lib/reference_latent.py``, which follows a prediction module's
        term, that term: zero, the model here has no module."""
        p = params["params"]
        T1, B = batch["done"].shape
        denom = float((T1 - 1) * B)
        pg = value = entropy = 0.0
        for c in range(B):
            t = column_terms(p, batch, c, spec, cast)
            values, bootstrap = t["baseline"][:-1], t["baseline"][-1]
            rewards = batch["rewards"][1:, c]
            if loss["reward_clip"] > 0:
                rewards = jnp.clip(
                    rewards, -loss["reward_clip"], loss["reward_clip"]
                )
            discounts = (
                1.0 - batch["done"][1:, c].astype(jnp.float32)
            ) * loss["discounting"]
            # The targets are constants of the optimisation.
            vs, adv = jax.lax.stop_gradient(vtrace_targets(
                t["target_lp"] - t["behavior_lp"], discounts, rewards,
                values, bootstrap,
            ))
            pg = pg - jnp.sum(t["target_lp"] * adv)
            value = value + 0.5 * jnp.sum((vs - values) ** 2)
            entropy = entropy + t["entropy"]
        total = (
            pg + loss["baseline_cost"] * value
            - loss["entropy_cost"] * entropy
        ) / denom
        zero = jnp.zeros((), jnp.float32)
        return total, {"mtp_loss": zero, "mtp_positions": zero}

    return loss_fn


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """[T, b] token ids -> logits [T, b, V], baseline [T, b] and the
        state handed on, whole: for the tests' small sizes."""
        p = params["params"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        logits, baseline, states = [], [], []
        for c in range(obs.shape[1]):
            h, state = trunk(
                p, obs[:, c].astype(jnp.int32), seg[:, c],
                column_state(core_state, c), spec, cast,
            )
            x = rms(h, p["final_norm"]["scale"], spec["eps"])
            logits.append(dot(x, p["head"]["kernel"], cast))
            baseline.append(
                dot(x, p["baseline"]["kernel"], cast)[:, 0]
                + p["baseline"]["bias"][0]
            )
            states.append(state)
        return (jnp.stack(logits, axis=1), jnp.stack(baseline, axis=1),
                tuple(jnp.stack(s) for s in zip(*states)))

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
