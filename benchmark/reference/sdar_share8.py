"""Plain reference of ``sdar_share8``: one chip's share of one pipeline
stage of SDAR-30B-A3B-Chat (JetLM, ``config.json``, ``model_type``
sdar_moe) as an actor-critic policy whose action is a denoising step,
float32 ``jax.numpy`` from the equations (SDAR, arXiv:2510.06303; block
diffusion's training pass over a clean and a noised copy under one mask,
BD3-LMs, arXiv:2503.09573; reinforcement learning over the trajectory of
denoising steps with a value beside the policy, TraceRL,
arXiv:2509.06949). Imports nothing of the program.

**A layer.** x is [rows, 2048]; H = 32 query heads on G = 4 key/value heads
of D = 128, query head i on key/value head i // 8.

    rms(x) = x / sqrt(mean(x^2) + 1e-6) * w
    x <- x + Attn(rms(x));   x <- x + Moe(rms(x))
    Attn: q = x W_q, k = x W_k, v = x W_v (no bias);
          q <- rms_head(q) * g_q, k <- rms_head(k) * g_k over the 128 of a
          head, one gain [128] each a layer, before the rotary;
          rotary over the whole head, half-split, theta 1e6, the row's
          position; scores * 128^-1/2; softmax over the keys the row sees;
          concat(heads) W_o
    Moe:  p = softmax(x W_r) over 128; the 8 largest, gates p / sum(p) over
          the chosen; sum_k g_k (silu(x Wg_k) * (x Wu_k)) Wd_k over the
          chosen experts *held here* (router ids first_expert .. + the rows
          of w_gate); what the absent experts would add is left out, as in
          the program.

**The rows.** One column of the batch is one packed sequence of L = D (N +
1) tokens x_i in blocks b(i) = i // D of D = 4; token i of blocks 0..N-1
was revealed at step r_i in [0, S) of its block, S = 2. The stack runs
over 1 + S copies, (1 + S) L rows, row (c, i) at index c L + i, copy 0 the
clean one and copy 1 + tau the state before step tau:

    input of (clean, i) = E[x_i]
    input of (tau, i)   = E[x_i] if r_i < tau and b(i) < N, else E[MASK]
    position of (c, i)  = i
    (c, i) sees (c', j) iff episode(i) = episode(j) and
           (c' = clean and b(j) < b(i))  or  (c' = c and b(j) = b(i))

written below as a boolean matrix from that definition, a block of query
rows at a time (:func:`seen_rows`). ``done`` lies on the step axis, frame u
= S b + tau; an episode begins at a block's first step: episode(i) = the
running count of done[S b(i)].

**The loss.** Token i < D N is scored in row (r_i, i): h_i the final norm
of that row, logits_i = h_i W_head, v_i = w_v . h_i + b_v. With u(i) = S
b(i) + r_i, G(u) the tokens of step u, lp_i = log softmax(logits_i)[x_i]
and lm_i the same of the batch's behaviour logits:

    log rho_u = sum_G(u) (lp_i - lm_i);    log pi_u = sum_G(u) lp_i
    H_u = sum_G(u) H(softmax(logits_i));   V_u = mean_G(u) v_i
    V_n = mean of v over block N's D rows of copy 1 (tau = 0)
    vs, adv = V-trace over the n = S N steps (rho and c clipped at 1 a
    step), a backward scan in Python order
    total = -mean_u(log pi_u adv_u) + 0.5 c_b mean_u (vs_u - V_u)^2
            - c_e mean_u H_u

The group sums are a segment sum over ``action_step`` as the batch gives
it. Blocks, the rows of the score matrix and the head with its loss are
computed a block at a time and rebuilt in the backward pass, so that no
[H, rows, rows] and no [tokens, vocabulary] array is ever held. ``cast``
rounds both operands of every matrix product (identity for the reference
proper; see ``lib/reference_train.py``).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference_train import vtrace_targets

# What the parameter shapes do not say: the published settings, and the
# sizes the configuration assumes (block, steps, mask_id).
PUBLISHED = {
    "head_dim": 128,
    "rope_theta": 1e6,
    "top_k": 8,
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-6,
    "block": 4,
    "steps": 2,
    "mask_id": 18725,
    "query_rows": 128,  # rows of the score matrix computed at a time
    "expert_rows": 2048,  # rows the experts' loop runs over at a time
    "head_rows": 1024,  # tokens of the head's logits computed at a time
}


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def dot(a, w, cast):
    return cast(a) @ cast(w)


def rotary(x, positions, theta):
    """x [rows, heads, D] turned by the rows' positions, half-split."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = positions.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def block_diffusion_rows(L, copies, episode, block):
    """What the visibility rule reads of every row ``c L + i``: its copy,
    its token's block and its token's episode."""
    i = jnp.tile(jnp.arange(L), copies)
    return jnp.repeat(jnp.arange(copies), L), i // block, episode[i // block]


def seen_rows(query, key):
    """The visibility rule as a boolean matrix [queries, keys], from its
    definition; ``query`` and ``key`` are ``(copy, block, episode)`` of
    the rows."""
    (cq, bq, eq), (ck, bk, ek) = (
        [t[:, None] for t in query], [t[None, :] for t in key]
    )
    return (eq == ek) & (
        ((ck == 0) & (bk < bq)) | ((ck == cq) & (bk == bq))
    )


def attention(z, p, positions, seen, spec, cast):
    """``seen(start, rows) -> bool [rows, all rows]``: which keys the
    query rows ``start .. start + rows`` see."""
    R, D = z.shape[0], spec["head_dim"]
    q = dot(z, p["q"]["kernel"], cast).reshape(R, -1, D)
    k = dot(z, p["k"]["kernel"], cast).reshape(R, -1, D)
    v = dot(z, p["v"]["kernel"], cast).reshape(R, -1, D)
    H, G = q.shape[1], k.shape[1]
    q = rotary(rms(q, p["q_norm"]["scale"], spec["eps"]), positions,
               spec["rope_theta"])
    k = rotary(rms(k, p["k_norm"]["scale"], spec["eps"]), positions,
               spec["rope_theta"])
    # each query head beside the key/value head it reads
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    rows = min(spec["query_rows"], R)
    assert R % rows == 0, (R, rows)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        s = jnp.einsum("ihd,jhd->hij", cast(qb), cast(k)) / math.sqrt(D)
        w = jax.nn.softmax(
            jnp.where(seen(start, rows)[None], s, -jnp.inf), axis=-1
        )
        return jnp.einsum("hij,jhd->ihd", cast(w), cast(v))

    o = jax.lax.map(block, jnp.arange(0, R, rows)).reshape(R, H * D)
    return dot(o, p["o"]["kernel"], cast)


def experts(z, p, spec, cast):
    """Every expert held applied to every row behind its gate, a block of
    ``expert_rows`` rows at a time (rebuilt in the backward pass: the
    loop's residuals, 16 experts' hidden rows, are a block's and not all
    24,576 rows')."""
    def rows(z):
        probs = jax.nn.softmax(dot(z, p["router"], cast), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, spec["top_k"])
        gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        def one_expert(y, expert):
            e, w_gate, w_up, w_down = expert
            # this expert's gate a row: 0 where the row did not choose it
            g = jnp.sum(
                jnp.where(top_i == spec["first_expert"] + e, gates, 0.0),
                axis=-1,
            )
            hidden = jax.nn.silu(dot(z, w_gate, cast)) * dot(z, w_up, cast)
            return y + g[:, None] * dot(hidden, w_down, cast), None

        # the loop over the experts held: a scan, so that the compiler
        # builds one expert's program
        y, _ = jax.lax.scan(
            one_expert, jnp.zeros_like(z),
            (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
             p["w_down"]),
        )
        return y

    return by_rows(rows, spec["expert_rows"], z).reshape(z.shape)


def stack(p, ids, positions, seen, spec, cast):
    """Token ids [rows] -> the last block's output [rows, d]; the blocks
    are stacked on their leaves' leading axis."""
    x = p["embed"]["embedding"][ids]

    @jax.checkpoint
    def block(x, bp):
        x = x + attention(
            rms(x, bp["norm1"]["scale"], spec["eps"]), bp["attn"], positions,
            seen, spec, cast,
        )
        return x + experts(
            rms(x, bp["norm2"]["scale"], spec["eps"]), bp["moe"], spec, cast
        )

    x, _ = jax.lax.scan(lambda x, bp: (block(x, bp), None), x, p["block_0"])
    return x


def by_rows(fn, rows, *arrays):
    """``fn`` over blocks of ``rows`` leading rows, each rebuilt in the
    backward pass; the blocks' results stacked."""
    T = arrays[0].shape[0]
    rows = math.gcd(rows, T)
    return jax.lax.map(
        lambda xs: jax.checkpoint(fn)(*xs),
        tuple(a.reshape(T // rows, rows, *a.shape[1:]) for a in arrays),
    )


def value(x, p, cast):
    return dot(x, p["baseline"]["kernel"], cast)[:, 0] + p["baseline"][
        "bias"
    ][0]


def scored_hidden(p, tokens, reveal, done, spec, cast):
    """One packed sequence: tokens and reveal steps [L], ``done`` on the
    step axis [S N + 1] -> the final norm of every token's scored row
    [L, d]: token i < D N from copy 1 + r_i, block N's from copy 1."""
    D, S = spec["block"], spec["steps"]
    L = tokens.shape[0]
    N = L // D - 1
    assert L == D * (N + 1) and done.shape[0] == S * N + 1, (L, done.shape)
    acted = jnp.arange(L) < D * N
    ids = jnp.concatenate([tokens] + [
        jnp.where((reveal < tau) & acted, tokens, spec["mask_id"])
        for tau in range(S)
    ])
    episode = jnp.cumsum(done[::S].astype(jnp.int32))  # of block b
    rows = block_diffusion_rows(L, 1 + S, episode, D)

    def seen(start, count):
        return seen_rows(
            [jax.lax.dynamic_slice_in_dim(t, start, count) for t in rows],
            rows,
        )

    h = stack(p, ids, jnp.tile(jnp.arange(L), 1 + S), seen, spec, cast)
    row = (1 + jnp.where(acted, reveal, 0)) * L + jnp.arange(L)
    return rms(h[row], p["final_norm"]["scale"], spec["eps"])


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's token terms and its values."""
    tokens = chunk["obs"]["tokens"][:, c].astype(jnp.int32)
    reveal = chunk["obs"]["reveal_step"][:, c].astype(jnp.int32)
    x = scored_hidden(p, tokens, reveal, chunk["done"][:, c], spec, cast)
    acted = chunk["actions"].shape[0]
    head = p["head"]["kernel"]

    def policy_rows(x, actions, behavior):
        logp = jax.nn.log_softmax(dot(x, head, cast), axis=-1)
        take = lambda lp: jnp.take_along_axis(  # noqa: E731
            lp, actions[:, None], axis=-1
        )[:, 0]
        return (take(logp), take(jax.nn.log_softmax(behavior, axis=-1)),
                -jnp.sum(jnp.exp(logp) * logp, axis=-1))

    target_lp, behavior_lp, entropy = (
        t.reshape(acted) for t in by_rows(
            policy_rows, spec["head_rows"], x[:acted],
            chunk["actions"][:, c], chunk["behavior_logits"][:, c],
        )
    )
    return {
        "target_lp": target_lp, "behavior_lp": behavior_lp,
        "entropy": entropy, "values": value(x, p, cast),
    }


def make_loss(spec):
    def loss_fn(params, batch, loss, cast):
        """The step's total loss (means over steps x B), and, for
        ``lib/reference_latent.py``, which follows a prediction module's
        term, that term: zero, the model here has no module."""
        p = params["params"]
        T1, B = batch["done"].shape
        steps = T1 - 1
        acted = batch["actions"].shape[0]
        pg = critic = entropy = 0.0
        for c in range(B):
            t = column_terms(p, batch, c, spec, cast)
            step = batch["action_step"][:, c]

            def group(x):
                return jax.ops.segment_sum(x, step, num_segments=steps)

            size = jnp.maximum(group(jnp.ones(acted, jnp.float32)), 1.0)
            values = group(t["values"][:acted]) / size
            bootstrap = jnp.mean(t["values"][acted:])
            log_pi = group(t["target_lp"])
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
                group(t["target_lp"] - t["behavior_lp"]), discounts, rewards,
                values, bootstrap,
            ))
            pg = pg - jnp.sum(log_pi * adv)
            critic = critic + 0.5 * jnp.sum((vs - values) ** 2)
            entropy = entropy + jnp.sum(t["entropy"])
        total = (
            pg + loss["baseline_cost"] * critic
            - loss["entropy_cost"] * entropy
        ) / float(steps * B)
        zero = jnp.zeros((), jnp.float32)
        return total, {"mtp_loss": zero, "mtp_positions": zero}

    return loss_fn


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """``obs`` {"tokens", "reveal_step"} [L, b], ``done`` [S N + 1, b]
        -> logits [D N, b, V] of the scored tokens, the values [L, b] (a
        token's, and block N's of copy 1 last) and the state handed on
        (none: ``()``), whole: for the tests' small sizes."""
        p = params["params"]
        logits, values = [], []
        for c in range(done.shape[1]):
            x = scored_hidden(
                p, obs["tokens"][:, c].astype(jnp.int32),
                obs["reveal_step"][:, c].astype(jnp.int32), done[:, c], spec,
                cast,
            )
            logits.append(dot(x[:-spec["block"]], p["head"]["kernel"], cast))
            values.append(value(x, p, cast))
        return jnp.stack(logits, axis=1), jnp.stack(values, axis=1), ()

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
