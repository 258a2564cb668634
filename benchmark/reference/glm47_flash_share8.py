"""Plain reference of ``glm47_flash_share8``: one expert-parallel chip's
share of a pipeline stage of GLM-4.7-Flash (zai-org, ``config.json``,
``model_type`` glm4_moe_lite) as a token-level actor-critic policy with its
multi-token-prediction module, float32 ``jax.numpy`` from the equations.
Imports nothing of the program.

x is [tokens, 2048]; one column of the batch is one packed sequence.

    rms(x) = x / sqrt(mean(x^2) + 1e-5) * w

    latent attention (every block):
      c_q = rms(z W_qa)  (768);  q = c_q W_qb as H heads of [nope 192 | rope 64]
      [c_kv | k_r] = z W_kva  (512 + 64);  c_kv = rms(c_kv)
      [k_nope 192 | v 256] a head = c_kv W_kvb
      rotary (half-split form, inv_freq_i = 1e6^(-2i/64)) on q's rope part
      and on k_r, which is ONE vector a position, the same for all heads
      key j visible to query i iff j <= i, both in one episode segment
        (segment = running count of `done`)
      a = softmax([q_nope | q_rope] . [k_nope | k_rope] / sqrt(256)) v
      out = concat(heads of 256) W_o

    block, dense (layer 0):   h = x + attn(rms1(x))
                              out = h + (silu(z Wg) * (z Wu)) Wd,  z = rms2(h)
    block, sparse:            s = sigmoid(z Wr) over all 64
                              S = the 4 largest of s + b   (b: correction bias)
                              g_e = 1.8 * s_e / sum_S s
                              out = h + sum over e in S held here of
                                    g_e * Expert_e(z)  +  Shared(z)
                              every expert and the shared one SwiGLU of 1536

    logits = rms_f(x) Whead;  baseline = rms_f(x) wv + b

    multi-token prediction (one module, DeepSeek-V3's form): with h the
    last block's output before rms_f and e the embedding,
      u_t = [rms_e(e_{t+1}) ; rms_h(h_t)] W_eh;  u = sparse block(u)
      logits2_t = rms_m(u_t) Whead         (for token t+2)
      mtp_loss = mean over {t : t+2 <= T, segment(t+2) = segment(t)} of
                 -log softmax(logits2_t)[token_{t+2}]

The share and the depth are read off the parameter tree: a block with an
``mlp`` is dense and one with a ``moe`` sparse; a block whose leaves carry
one more leading axis is that many identical blocks, run as a scan; the
experts held are router ids ``first_expert`` .. + the rows of ``w_gate``;
the vocabulary is the rows held. What the absent experts would add is left
out, as in the program. ``b`` is a constant of the optimisation
(``stop_gradient``): its gradient is exactly zero.

Blocks, the rows of the score matrix, and both heads with their losses are
computed a block at a time and rebuilt in the backward pass, so that no
[H, T, T] and no [T, vocabulary] array is ever held. ``cast`` rounds both
operands of every matrix product (identity for the reference proper; see
``lib/reference_train.py``).
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference_train import vtrace_targets

# What the parameter shapes do not say: the published settings.
PUBLISHED = {
    "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64,
    "v_head_dim": 256,
    "top_k": 4,
    "routed_scaling_factor": 1.8,
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-5,
    "theta": 1000000.0,
    "query_rows": 256,  # rows of the score matrix computed at a time
    "head_rows": 1024,  # positions of a head's logits computed at a time
}


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, cos, sin):
    """x [T, heads, D]; cos, sin [T, D / 2]: pairs (i, i + D/2) turn by
    the angle of frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def dot(a, w, cast):
    return cast(a) @ cast(w)


def attention(z, p, seg, spec, cast):
    T = z.shape[0]
    nope, rot, dv = (
        spec["qk_nope_head_dim"], spec["qk_rope_head_dim"], spec["v_head_dim"]
    )
    eps = spec["eps"]
    c_q = rms(dot(z, p["q_a"]["kernel"], cast), p["q_a_norm"]["scale"], eps)
    q = dot(c_q, p["q_b"]["kernel"], cast).reshape(T, -1, nope + rot)
    H = q.shape[1]
    kva = dot(z, p["kv_a"]["kernel"], cast)
    c_kv = rms(kva[:, :-rot], p["kv_a_norm"]["scale"], eps)
    k_r = kva[:, -rot:]
    kv = dot(c_kv, p["kv_b"]["kernel"], cast).reshape(T, H, nope + dv)
    inv_freq = jnp.asarray(
        [spec["theta"] ** (-2.0 * i / rot) for i in range(rot // 2)],
        jnp.float32,
    )
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], cos, sin)], axis=-1
    )
    k_rope = rotate(k_r[:, None, :], cos, sin)  # [T, 1, rot]: every head's
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_rope, H, axis=1)], axis=-1
    )
    v = kv[..., nope:]
    rows = min(spec["query_rows"], T)
    assert T % rows == 0, (T, rows)
    j = jnp.arange(T)

    @jax.checkpoint
    def block(start):
        i = start + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        s = jnp.einsum("ihd,jhd->hij", cast(qb), cast(k)) / math.sqrt(
            nope + rot
        )
        seen = (j[None, :] <= i[:, None]) & (
            jax.lax.dynamic_slice_in_dim(seg, start, rows)[:, None]
            == seg[None, :]
        )
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", cast(w), cast(v))

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * dv)
    return dot(o, p["o"]["kernel"], cast)


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

    # every expert held over every token behind its mask, as a scan: one
    # expert's program
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]),
    )
    return y + gated(z, p["shared"], cast)


def block(x, bp, seg, spec, cast):
    eps = spec["eps"]
    h = x + attention(rms(x, bp["norm1"]["scale"], eps), bp["attn"], seg,
                      spec, cast)
    z = rms(h, bp["norm2"]["scale"], eps)
    if "mlp" in bp:
        return h + gated(z, bp["mlp"], cast)
    return h + experts(z, bp["moe"], spec, cast)


def blocks(x, bp, seg, spec, cast):
    """One block, or as many as its leaves' leading axis says, each
    rebuilt in the backward pass."""
    one = jax.checkpoint(lambda x, bp: block(x, bp, seg, spec, cast))
    if bp["norm1"]["scale"].ndim == 1:
        return one(x, bp)
    return jax.lax.scan(lambda x, bp: (one(x, bp), None), x, bp)[0]


def trunk(p, tokens, seg, spec, cast):
    """tokens [T] -> the embedding [T, d] and the last block's output."""
    e = p["embed"]["embedding"][tokens]
    x = e
    count = sum(1 for name in p if name.startswith("block_"))
    for i in range(count):
        x = blocks(x, p[f"block_{i}"], seg, spec, cast)
    return e, x


def mtp_hidden(p, e, h, seg, spec, cast):
    """The module's normed output [T, d]: row t predicts token t+2."""
    m, eps = p["mtp"], spec["eps"]
    u = dot(
        jnp.concatenate(
            [rms(jnp.roll(e, -1, axis=0), m["enorm"]["scale"], eps),
             rms(h, m["hnorm"]["scale"], eps)], axis=-1,
        ),
        m["eh_proj"]["kernel"], cast,
    )
    u = blocks(u, m["block"], seg, spec, cast)
    return rms(u, m["final_norm"]["scale"], eps)


def mtp_valid(seg):
    T = seg.shape[0]
    return (jnp.roll(seg, -2) == seg) & (jnp.arange(T) < T - 2)


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


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's sums: what the losses are means of."""
    tokens = chunk["obs"][:, c].astype(jnp.int32)
    seg = jnp.cumsum(chunk["done"][:, c].astype(jnp.int32))
    T1 = tokens.shape[0]
    e, h = trunk(p, tokens, seg, spec, cast)
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

    u = mtp_hidden(p, e, h, seg, spec, cast)
    valid = mtp_valid(seg)

    def mtp_rows(u, target, valid):
        logp = jax.nn.log_softmax(dot(u, head, cast), axis=-1)
        nll = -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(valid, nll, 0.0))

    mtp_sum = jnp.sum(by_rows(
        mtp_rows, spec["head_rows"], u, jnp.roll(tokens, -2), valid
    ))
    return {
        "target_lp": target_lp, "behavior_lp": behavior_lp,
        "entropy": jnp.sum(entropy), "baseline": baseline,
        "mtp_sum": mtp_sum, "mtp_count": jnp.sum(valid).astype(jnp.float32),
    }


def make_loss(spec):
    def loss_fn(params, batch, loss, cast):
        """The step's total loss and its parts: the IMPALA loss of
        ``lib/reference_train.py`` (means over T x B) plus
        ``loss["mtp_cost"]`` times the module's cross-entropy (a mean over
        the positions that count, all columns)."""
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
            mtp_sum, mtp_count = mtp_sum + t["mtp_sum"], mtp_count + t[
                "mtp_count"
            ]
        mtp_loss = mtp_sum / jnp.maximum(mtp_count, 1.0)
        total = (
            pg + loss["baseline_cost"] * value
            - loss["entropy_cost"] * entropy
        ) / denom + loss["mtp_cost"] * mtp_loss
        return total, {"mtp_loss": mtp_loss, "mtp_positions": mtp_count}

    return loss_fn


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """[T, b] token ids -> logits [T, b, V], baseline [T, b], whole:
        for the tests' small sizes."""
        p = params["params"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        logits, baseline = [], []
        for c in range(obs.shape[1]):
            _, h = trunk(p, obs[:, c].astype(jnp.int32), seg[:, c], spec, cast)
            x = rms(h, p["final_norm"]["scale"], spec["eps"])
            logits.append(dot(x, p["head"]["kernel"], cast))
            baseline.append(
                dot(x, p["baseline"]["kernel"], cast)[:, 0]
                + p["baseline"]["bias"][0]
            )
        return jnp.stack(logits, axis=1), jnp.stack(baseline, axis=1), core_state

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
