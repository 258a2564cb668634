"""Plain reference of ``xing4_share8``: one expert-parallel chip's share of
a pipeline stage of Xing4.0-29B-A4B (XingChen-AGI, ``config.json``,
``model_type`` xing4_0) as a token-level actor-critic policy, float32
``jax.numpy`` from the equations. Imports nothing of the program; the
gated MLPs, the router's rule and the held experts are
``reference/glm47_flash_share8.py``'s functions, which are the same
equations at other numbers.

n = 4 streams, C = 3584; one column of the batch is one packed sequence.

    rms_w(x) = x / sqrt(mean(x^2) + 1e-6) * w

    streams  X [tokens, n, C];  X_0[i] = embedding(token) for every i

    a sublayer F (a block's attention or its MLP), with its own
    phi [n C, n^2 + 2n], b [n^2 + 2n], alpha = (a_pre, a_post, a_res):
      xt    = vec(X) / sqrt(mean(vec(X)^2) + 1e-6)        no learned gain
      m     = xt phi
      Hpre  = sigmoid(a_pre m[0:n] + b[0:n])
      Hpost = 2 sigmoid(a_post m[n:2n] + b[n:2n])
      M     = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), -30, 30))
      20 times:  M = M / (rowsum(M) + 1e-6);  M = M / (colsum(M) + 1e-6)
      h     = sum_i Hpre[i] X[i]
      y     = F(rms_w(h))            w: the block's norm1 or norm2
      X'[i] = sum_j M[i, j] X[j] + Hpost[i] y
    a block = the attention sublayer, then the MLP sublayer
    after the last block: x = sum_i X[i]
    logits = rms_f(x) Whead;  baseline = rms_f(x) wv + b

    latent attention: glm47_flash_share8's, with heads of [nope 128 |
    rope 64] queries and keys and 128 values, and
      inv_freq: YaRN over the 64 rotary dimensions (theta 1e4, factor 64,
        original context 4096, beta 32 / 1): a frequency that turns more
        than 32 times over 4096 positions stays, one that turns less than
        once is divided by 64, a linear ramp over the index between (its
        ends rounded outwards); cos and sin scaled by
        mscale(64, 1) / mscale(64, 1) = 1
      scores scaled by 192^-1/2 mscale^2, mscale = 0.1 ln(64) + 1
      out = concat(heads of 128) W_o
    dense MLP (layer 0) 9216; sparse MLP: sigmoid scores over 64, the 4
    largest of s + b, gates s / sum(s) x 2, experts and the shared one
    SwiGLU of 1024.

The mixing (``xt``, ``m``, the sigmoids, the iterations, both weighted
sums) is float32 whatever ``cast`` says: the configuration states it so,
and ``cast`` rounds the operands of the products the program computes in
its compute dtype. The gradient goes through all 20 iterations and
through the clip (zero outside it).

The share and the depth are read off the parameter tree as
``glm47_flash_share8`` reads them; a block whose leaves carry one more
leading axis is that many identical blocks, run as a scan (one block's
code a kind). Blocks, the rows of the score matrix and the head with its
loss are computed a block at a time and rebuilt in the backward pass.
"""

import math

import jax
import jax.numpy as jnp

from benchmark.lib.reference_train import vtrace_targets
from benchmark.reference.glm47_flash_share8 import (by_rows, dot, experts,
                                                    gated, rms, rotate)

# What the parameter shapes do not say: the published settings.
PUBLISHED = {
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "v_head_dim": 128,
    "top_k": 4,
    "routed_scaling_factor": 2.0,
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-6,
    "theta": 10000.0,
    "yarn_factor": 64.0,
    "yarn_original": 4096,
    "yarn_beta_fast": 32.0,
    "yarn_beta_slow": 1.0,
    "mscale": 1.0,
    "mscale_all_dim": 1.0,
    "streams": 4,
    "sinkhorn_iters": 20,
    "hc_eps": 1e-6,
    "res_clamp": (-30.0, 30.0),
    "query_rows": 256,  # rows of the score matrix computed at a time
    "head_rows": 1024,  # positions of the head's logits computed at a time
}


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(spec):
    """[rot / 2] frequencies, as DeepSeek-V3's rotary embedding blends
    them."""
    rot, theta = spec["qk_rope_head_dim"], spec["theta"]

    def index_of(turns):  # the (real) index whose frequency turns so often
        return rot * math.log(
            spec["yarn_original"] / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(index_of(spec["yarn_beta_fast"])), 0)
    high = min(math.ceil(index_of(spec["yarn_beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(rot // 2):
        plain = theta ** (-2.0 * i / rot)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / spec["yarn_factor"] * ramp + plain * (1 - ramp))
    return jnp.asarray(out, jnp.float32)


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
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * yarn_inv_freq(spec)
    turn = yarn_mscale(spec["yarn_factor"], spec["mscale"]) / yarn_mscale(
        spec["yarn_factor"], spec["mscale_all_dim"]
    )
    cos, sin = jnp.cos(angle) * turn, jnp.sin(angle) * turn
    q = jnp.concatenate(
        [q[..., :nope], rotate(q[..., nope:], cos, sin)], axis=-1
    )
    k_rope = rotate(k_r[:, None, :], cos, sin)  # [T, 1, rot]: every head's
    k = jnp.concatenate(
        [kv[..., :nope], jnp.repeat(k_rope, H, axis=1)], axis=-1
    )
    v = kv[..., nope:]
    scale = (nope + rot) ** -0.5 * yarn_mscale(
        spec["yarn_factor"], spec["mscale_all_dim"]
    ) ** 2
    rows = min(spec["query_rows"], T)
    assert T % rows == 0, (T, rows)
    j = jnp.arange(T)

    @jax.checkpoint
    def block(start):
        i = start + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, 0)
        s = jnp.einsum("ihd,jhd->hij", cast(qb), cast(k)) * scale
        seen = (j[None, :] <= i[:, None]) & (
            jax.lax.dynamic_slice_in_dim(seg, start, rows)[:, None]
            == seg[None, :]
        )
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", cast(w), cast(v))

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * dv)
    return dot(o, p["o"]["kernel"], cast)


def mixing(X, p, spec):
    """X [T, n, C] -> Hpre [T, n], Hpost [T, n], Hres [T, n, n]."""
    T, n, C = X.shape
    v = X.reshape(T, n * C)
    xt = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                           + spec["eps"])
    m = xt @ p["phi"]
    a, b = p["alpha"], p["b"]
    pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    lo, hi = spec["res_clamp"]
    M = jnp.exp(jnp.clip(
        a[2] * m[:, 2 * n:].reshape(T, n, n) + b[2 * n:].reshape(n, n),
        lo, hi,
    ))
    for _ in range(spec["sinkhorn_iters"]):
        M = M / (jnp.sum(M, axis=2, keepdims=True) + spec["hc_eps"])
        M = M / (jnp.sum(M, axis=1, keepdims=True) + spec["hc_eps"])
    return pre, post, M


def sublayer(X, mix, w, f, spec):
    pre, post, M = mixing(X, mix, spec)
    h = jnp.einsum("ti,tic->tc", pre, X)
    y = f(rms(h, w, spec["eps"]))
    return jnp.einsum("tij,tjc->tic", M, X) + post[:, :, None] * y[:, None, :]


def block(X, bp, seg, spec, cast):
    X = sublayer(
        X, bp["hc_attn"], bp["norm1"]["scale"],
        lambda z: attention(z, bp["attn"], seg, spec, cast), spec,
    )
    if "mlp" in bp:
        return sublayer(
            X, bp["hc_mlp"], bp["norm2"]["scale"],
            lambda z: gated(z, bp["mlp"], cast), spec,
        )
    return sublayer(
        X, bp["hc_mlp"], bp["norm2"]["scale"],
        lambda z: experts(z, bp["moe"], spec, cast), spec,
    )


def blocks(X, bp, seg, spec, cast):
    """One block, or as many as its leaves' leading axis says, each
    rebuilt in the backward pass."""
    one = jax.checkpoint(lambda X, bp: block(X, bp, seg, spec, cast))
    if bp["norm1"]["scale"].ndim == 1:
        return one(X, bp)
    return jax.lax.scan(lambda X, bp: (one(X, bp), None), X, bp)[0]


def trunk(p, tokens, seg, spec, cast):
    """tokens [T] -> the streams' sum after the last block, [T, C]."""
    e = p["embed"]["embedding"][tokens]
    X = jnp.repeat(e[:, None, :], spec["streams"], axis=1)
    count = sum(1 for name in p if name.startswith("block_"))
    for i in range(count):
        X = blocks(X, p[f"block_{i}"], seg, spec, cast)
    return jnp.sum(X, axis=1)


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's sums: what the losses are means of."""
    tokens = chunk["obs"][:, c].astype(jnp.int32)
    seg = jnp.cumsum(chunk["done"][:, c].astype(jnp.int32))
    T1 = tokens.shape[0]
    x = rms(trunk(p, tokens, seg, spec, cast), p["final_norm"]["scale"],
            spec["eps"])
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
        """[T, b] token ids -> logits [T, b, V], baseline [T, b], whole:
        for the tests' small sizes."""
        p = params["params"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        logits, baseline = [], []
        for c in range(obs.shape[1]):
            x = rms(
                trunk(p, obs[:, c].astype(jnp.int32), seg[:, c], spec, cast),
                p["final_norm"]["scale"], spec["eps"],
            )
            logits.append(dot(x, p["head"]["kernel"], cast))
            baseline.append(
                dot(x, p["baseline"]["kernel"], cast)[:, 0]
                + p["baseline"]["bias"][0]
            )
        return jnp.stack(logits, axis=1), jnp.stack(baseline, axis=1), core_state

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
