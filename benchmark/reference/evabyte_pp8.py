"""Plain reference of ``evabyte_pp8``: one pipeline stage's four whole
layers of EvaByte (EvaByte/EvaByte, ``config.json``, ``model_type``
evabyte, ``attention_class`` eva) with the embedding and the head, as a
byte-level actor-critic policy, float32 ``jax.numpy`` from the equations.
Imports nothing of the program; ``dot``, ``rotate``, ``gated`` and
``by_rows`` are ``reference/glm47_flash_share8.py``'s lines, which are the
same equations.

d = 128 a head, W = 2048 a window, c = 16 a chunk, u = rms1(x); one column
of the batch is one packed sequence, positions 0..T-1, never reset.

    rms(x)   = x / sqrt(mean(x^2) + 1e-5) * (1 + g)
    q, k, v  = rotary(u W_q), rotary(u W_k), u W_v     32 heads of 128
    chunk j  = positions [c j, c j + c - 1];  P_j = those of them in the
               episode of the chunk's last position
    a_{j,s}  = softmax over s in P_j of (k_s . phi_h) d^-1/2
    kt_j     = sum_s a_{j,s} k_s + mu_h;   vt_j = sum_s a_{j,s} v_s
    L_t      = {s: floor(s/W) = floor(t/W), s <= t, episode(s) = episode(t)}
    R_t      = {j: floor(c j / W) < floor(t/W),
                   episode(c j + c - 1) = episode(t)}
    o_t      = softmax over L_t and R_t together of
               [q_t . k_s d^-1/2 (s in L_t) | q_t . kt_j d^-1/2 (j in R_t)]
               applied to [v_s | vt_j]
    h        = x + concat_h(o) W_o
    y        = h + W_down(silu(W_gate rms2(h)) * (W_up rms2(h)))

    logits   = rms_f(x) W_head, [8, 320] head-major: the first 320 columns
               are the policy (byte t+1), columns 320 i .. 320 i + 319 head
               i's (byte t+1+i); baseline = rms_f(x) w_v + b

The scores against the window's own keys and against the summaries are
concatenated and put through ONE softmax, a block of query rows at a time,
rebuilt in the backward pass. ``cast`` rounds the operands of the products
the program computes in its compute dtype (the projections, the two score
products, the two weighted sums of values, the MLP, the heads); the chunk
pooling (its scores against ``phi``, its softmax, both pooled sums) is
float32 whatever ``cast`` says, as the program computes it.

The loss is ``lib/reference_train.py``'s IMPALA loss (means over T x B)
plus ``loss["mtp_cost"]`` times the mean, over every (position, head) that
counts, of heads 1..7's cross-entropy: position t counts for head i if
byte t+1+i exists and lies in t's episode.
"""

import jax
import jax.numpy as jnp

from benchmark.lib.reference_train import vtrace_targets
from benchmark.reference.glm47_flash_share8 import (by_rows, dot, gated,
                                                    rotate)

# What the parameter shapes do not say: the published settings.
PUBLISHED = {
    "head_dim": 128,
    "window_size": 2048,
    "chunk_size": 16,
    "vocab_size": 320,
    "eps": 1e-5,
    "theta": 100000.0,
    "query_rows": 128,  # rows of the score matrix computed at a time
    "head_rows": 1024,  # positions of the heads' logits computed at a time
    "mlp_rows": 2048,  # positions of an MLP computed at a time
}


def rms(x, g, eps):
    """The norm with a unit offset: the parameter is the gain less 1."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * (1.0 + g)


def summaries(k, v, seg, phi, mu, c):
    """k, v [T, H, d] -> kt, vt [n, H, d] with n = ceil(T / c), and each
    chunk's episode [n]. A last chunk cut short by T pools what it has."""
    T, H, d = k.shape
    n = -(-T // c)
    pad = n * c - T
    kp = jnp.pad(k, ((0, pad), (0, 0), (0, 0))).reshape(n, c, H, d)
    vp = jnp.pad(v, ((0, pad), (0, 0), (0, 0))).reshape(n, c, H, d)
    segp = jnp.pad(seg, (0, pad), mode="edge").reshape(n, c)
    episode = segp[:, -1]
    own = (segp == episode[:, None]) & (
        jnp.arange(n * c) < T).reshape(n, c)
    s = jnp.einsum("nchd,hd->nch", kp, phi) * d ** -0.5
    a = jax.nn.softmax(jnp.where(own[:, :, None], s, -jnp.inf), axis=1)
    kt = jnp.einsum("nch,nchd->nhd", a, kp) + mu[None]
    vt = jnp.einsum("nch,nchd->nhd", a, vp)
    return kt, vt, episode


def attention(z, p, seg, spec, cast):
    T = z.shape[0]
    d, W, c = spec["head_dim"], spec["window_size"], spec["chunk_size"]
    q = dot(z, p["q"]["kernel"], cast).reshape(T, -1, d)
    k = dot(z, p["k"]["kernel"], cast).reshape(T, -1, d)
    v = dot(z, p["v"]["kernel"], cast).reshape(T, -1, d)
    H = q.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * (
        spec["theta"] ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    )
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    kt, vt, chunk_episode = summaries(k, v, seg, p["phi"], p["mu"], c)
    chunk_window = (jnp.arange(kt.shape[0]) * c) // W
    rows = min(spec["query_rows"], T)
    assert T % rows == 0, (T, rows)
    j = jnp.arange(T)

    @jax.checkpoint
    def block(start):
        i = start + jnp.arange(rows)
        episode = jax.lax.dynamic_slice_in_dim(seg, start, rows)
        qb = cast(jax.lax.dynamic_slice_in_dim(q, start, rows, 0))
        local = jnp.einsum("ihd,jhd->hij", qb, cast(k)) * d ** -0.5
        in_window = (
            (j[None, :] <= i[:, None])
            & (j[None, :] // W == i[:, None] // W)
            & (episode[:, None] == seg[None, :])
        )
        earlier = jnp.einsum("ihd,nhd->hin", qb, cast(kt)) * d ** -0.5
        in_earlier = (
            (chunk_window[None, :] < i[:, None] // W)
            & (chunk_episode[None, :] == episode[:, None])
        )
        w = jax.nn.softmax(jnp.concatenate([
            jnp.where(in_window[None], local, -jnp.inf),
            jnp.where(in_earlier[None], earlier, -jnp.inf),
        ], axis=-1), axis=-1)
        return (
            jnp.einsum("hij,jhd->ihd", cast(w[..., :T]), cast(v))
            + jnp.einsum("hin,nhd->ihd", cast(w[..., T:]), cast(vt))
        )

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * d)
    return dot(o, p["o"]["kernel"], cast)


def block(x, bp, seg, spec, cast):
    h = x + attention(
        rms(x, bp["norm1"]["scale"], spec["eps"]), bp["attn"], seg, spec,
        cast,
    )
    # the MLP is a position's own: a block of rows at a time, rebuilt in
    # the backward pass, so that its hidden rows of 11,008 fit
    return by_rows(
        lambda h: h + gated(
            rms(h, bp["norm2"]["scale"], spec["eps"]), bp["mlp"], cast),
        spec["mlp_rows"], h,
    ).reshape(h.shape)


def blocks(x, bp, seg, spec, cast):
    """One block, or as many as its leaves' leading axis says, each
    rebuilt in the backward pass."""
    one = jax.checkpoint(lambda x, bp: block(x, bp, seg, spec, cast))
    if bp["norm1"]["scale"].ndim == 1:
        return one(x, bp)
    return jax.lax.scan(lambda x, bp: (one(x, bp), None), x, bp)[0]


def trunk(p, tokens, seg, spec, cast):
    """tokens [T] -> the last block's output under the final norm."""
    x = p["embed"]["embedding"][tokens]
    count = sum(1 for name in p if name.startswith("block_"))
    for i in range(count):
        x = blocks(x, p[f"block_{i}"], seg, spec, cast)
    return rms(x, p["final_norm"]["scale"], spec["eps"])


def further_targets(tokens, seg, heads):
    """For heads 1..heads-1: the byte each is asked for [T, heads - 1] and
    whether the position counts for it."""
    T = tokens.shape[0]
    t = jnp.arange(T)
    target, valid = [], []
    for i in range(1, heads):
        ahead = jnp.minimum(t + 1 + i, T - 1)
        target.append(tokens[ahead])
        valid.append((t + 1 + i < T) & (seg[ahead] == seg))
    return jnp.stack(target, axis=1), jnp.stack(valid, axis=1)


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's sums: what the losses are means of."""
    tokens = chunk["obs"][:, c].astype(jnp.int32)
    seg = jnp.cumsum(chunk["done"][:, c].astype(jnp.int32))
    T1, V = tokens.shape[0], spec["vocab_size"]
    x = trunk(p, tokens, seg, spec, cast)
    baseline = dot(x, p["baseline"]["kernel"], cast)[:, 0] + p["baseline"][
        "bias"
    ][0]
    head = p["head"]["kernel"]
    heads = head.shape[-1] // V
    # the policy's T rows, padded by the bootstrap row (unused) so that
    # the rows split into blocks
    actions = jnp.concatenate([chunk["actions"][:, c], jnp.zeros(1, jnp.int32)])
    behavior = jnp.concatenate(
        [chunk["behavior_logits"][:, c], jnp.zeros((1, V), jnp.float32)]
    )
    target, valid = further_targets(tokens, seg, heads)

    def rows(x, actions, behavior, target, valid):
        logits = dot(x, head, cast).reshape(-1, heads, V)
        logp = jax.nn.log_softmax(logits[:, 0], axis=-1)
        take = lambda lp, a: jnp.take_along_axis(  # noqa: E731
            lp, a[..., None], axis=-1
        )[..., 0]
        nll = -take(jax.nn.log_softmax(logits[:, 1:], axis=-1), target)
        return (take(logp, actions),
                take(jax.nn.log_softmax(behavior, axis=-1), actions),
                -jnp.sum(jnp.exp(logp) * logp, axis=-1),
                jnp.sum(jnp.where(valid, nll, 0.0), axis=-1))

    target_lp, behavior_lp, entropy, nll = (
        t.reshape(T1) for t in by_rows(
            rows, spec["head_rows"], x, actions, behavior, target, valid)
    )
    return {
        "target_lp": target_lp[:-1], "behavior_lp": behavior_lp[:-1],
        "entropy": jnp.sum(entropy[:-1]), "baseline": baseline,
        "mtp_sum": jnp.sum(nll),
        "mtp_count": jnp.sum(valid).astype(jnp.float32),
    }


def make_loss(spec):
    def loss_fn(params, batch, loss, cast):
        """The step's total loss and the further heads' term with its
        count, as ``lib/reference_latent.py`` follows them."""
        p = params["params"]
        T1, B = batch["done"].shape
        denom = float((T1 - 1) * B)
        pg = value = entropy = mtp_sum = mtp_count = 0.0
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
            mtp_sum = mtp_sum + t["mtp_sum"]
            mtp_count = mtp_count + t["mtp_count"]
        mtp_loss = mtp_sum / jnp.maximum(mtp_count, 1.0)
        total = (
            pg + loss["baseline_cost"] * value
            - loss["entropy_cost"] * entropy
        ) / denom + loss["mtp_cost"] * mtp_loss
        return total, {"mtp_loss": mtp_loss, "mtp_positions": mtp_count}

    return loss_fn


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """[T, b] byte ids -> the policy's logits [T, b, V], baseline
        [T, b], whole: for the tests' small sizes."""
        p = params["params"]
        V = spec["vocab_size"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        logits, baseline = [], []
        for c in range(obs.shape[1]):
            x = trunk(p, obs[:, c].astype(jnp.int32), seg[:, c], spec, cast)
            logits.append(dot(x, p["head"]["kernel"][:, :V], cast))
            baseline.append(
                dot(x, p["baseline"]["kernel"], cast)[:, 0]
                + p["baseline"]["bias"][0]
            )
        return jnp.stack(logits, axis=1), jnp.stack(baseline, axis=1), core_state

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
