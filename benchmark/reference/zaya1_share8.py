"""Plain reference of ``zaya1_share8``: one chip's share of one pipeline
stage of ZAYA1-8B (Zyphra, ``config.json``, ``model_type`` zaya) as a
token-level actor-critic policy, float32 ``jax.numpy`` from the equations
(Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1 report,
arXiv:2511.17127). Imports nothing of the program.

x is [tokens, 2048]; one column of the batch is one packed sequence; H = 8
query heads, G = 2 key/value heads, D = 128; query head i reads key/value
head i // 4.

    rms(x) = x / sqrt(mean(x^2) + 1e-5) * w
    a sublayer s with its function F_s and four learned vectors:
      x <- a_r (x + b_r) + a_y (F_s(rms_s(x)) + b_y)

    attention sublayer, h = rms(x):
      qt = h W_q  [T, H D];   kt = h W_k  [T, G D]
      v  = [h_t W_v1 | h_{t-1} W_v2]   key/value head 0 of the token
           itself, head 1 of the token before it
      c  = conv1(conv0([qt | kt]))
           conv0: depthwise, 2 taps a channel, a bias
                  u_t[j] = w0[0, j] a_{t-1}[j] + w0[1, j] a_t[j] + b0[j]
           conv1: 2 taps, one group of 128 channels a head, a bias
                  y_t[g] = u_{t-1}[g] W1[0, g] + u_t[g] W1[1, g] + b1[g]
      m_i = (qt_i + kt_{i // 4}) / 2
      q_i = c^q_i + m_i;   k_g = c^k_g + mean over i in g of m_i
      qh_i = sqrt(D) q_i / sqrt(|q_i|^2 + 1e-6)
      kh_g = tau_g sqrt(D) k_g / sqrt(|k_g|^2 + 1e-6)
      rotary (half-split, theta 5e6) on the first 64 of a head's 128
        dimensions of qh and kh, positions 0..T-1
      key j visible to query i iff j <= i, both in one episode segment
        (segment = running count of `done`)
      o = softmax(qh . kh / sqrt(D)) v;   y = concat(heads) W_o
    A row before an episode's first (a_{t-1}, u_{t-1}, h_{t-1}) reads as
    zero, and so does a row before the call's first.

    expert sublayer, h = rms(x), z_l the router's state of layer l:
      z_l = h W_rd + b_rd + gamma_l * z_{l-1}       (z_{-1} = 0)
      u = rms_256(z_l);  a1 = gelu(u W_1 + b_1);  a2 = gelu(a1 W_2 + b_2)
      p = softmax(a2 W_3)  over the 16 experts and the choice of none
      e = argmax(p + beta)               (beta: no gradient)
      y = p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e   if expert e is held here
      y = 0                               if it is not, or e is no expert

    logits = rms_f(x) E^T (the embedding's rows held);
    baseline = rms_f(x) wv + b

The share and the depth are read off the parameter tree: the blocks are
stacked on their leaves' leading axis and run as a scan; the experts held
are router ids ``first_expert`` .. + the rows of ``w_gate``; the heads are
the projections' widths over ``head_dim``; the vocabulary is the
embedding's rows. What the absent experts would add is left out, as in
the program.

Blocks, the rows of the score matrix and the head with its loss are
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
    "head_dim": 128,
    "rotary_dim": 64,  # partial_rotary_factor 0.5
    "rope_theta": 5e6,
    "num_experts": 16,  # the router's further columns are no expert
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-5,
    "l2_eps": 1e-6,
    "query_rows": 256,  # rows of the score matrix computed at a time
    "head_rows": 1024,  # positions of the head's logits computed at a time
}


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def dot(a, w, cast):
    return cast(a) @ cast(w)


def before(x, seg, back=1):
    """Row ``t - back`` of ``x`` at row ``t``; zero where that row is of
    another episode, or before the first."""
    T = x.shape[0]
    rows = jnp.concatenate([jnp.zeros_like(x[:back]), x[:T - back]])
    ids = jnp.concatenate([jnp.full((back,), -1, seg.dtype), seg[:T - back]])
    same = (ids == seg).reshape((T,) + (1,) * (x.ndim - 1))
    return jnp.where(same, rows, 0.0)


def rotary(x, spec):
    """x [T, heads, D]: the first ``rotary_dim`` dimensions turned by the
    position, the half-split form."""
    R = spec["rotary_dim"]
    inv = spec["rope_theta"] ** (-jnp.arange(0, R, 2, dtype=jnp.float32) / R)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2 = x[..., :R // 2], x[..., R // 2:R]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., R:]], axis=-1
    )


def attention(z, p, seg, spec, cast):
    T, D = z.shape[0], spec["head_dim"]
    qt = dot(z, p["q"]["kernel"], cast)
    kt = dot(z, p["k"]["kernel"], cast)
    H, G = qt.shape[-1] // D, kt.shape[-1] // D
    v = jnp.concatenate(
        [dot(z, p["v_own"]["kernel"], cast),
         before(dot(z, p["v_prev"]["kernel"], cast), seg)], axis=-1,
    ).reshape(T, G, D)
    a = jnp.concatenate([qt, kt], axis=-1)
    u = p["conv0"][0] * before(a, seg) + p["conv0"][1] * a + p["conv0_bias"]
    u = u.reshape(T, H + G, D)
    c = (
        jnp.einsum("tgd,gde->tge", cast(before(u, seg)), cast(p["conv1"][0]))
        + jnp.einsum("tgd,gde->tge", cast(u), cast(p["conv1"][1]))
        + p["conv1_bias"].reshape(H + G, D)
    )
    qt, kt = qt.reshape(T, G, H // G, D), kt.reshape(T, G, 1, D)
    m = (qt + kt) / 2
    q = c[:, :H] + m.reshape(T, H, D)
    k = c[:, H:] + jnp.mean(m, axis=2)

    def l2norm(x):
        return math.sqrt(D) * x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + spec["l2_eps"]
        )

    q = rotary(l2norm(q), spec)
    k = rotary(l2norm(k) * p["temperature"][:, None], spec)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
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

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * D)
    return dot(o, p["o"]["kernel"], cast)


def route(h, p, z, spec, cast):
    """The choice [T], its probability [T] and the router's state."""
    def layer(x, q):
        return dot(x, q["kernel"], cast) + q["bias"]

    z = layer(h, p["router_down"]) + p["router_gamma"] * z
    u = rms(z, p["router_norm"]["scale"], spec["eps"])
    for name in ("router_1", "router_2"):
        u = jax.nn.gelu(layer(u, p[name]), approximate=False)
    prob = jax.nn.softmax(dot(u, p["router_out"]["kernel"], cast), axis=-1)
    chosen = jnp.argmax(
        prob + jax.lax.stop_gradient(p["e_score_correction_bias"]), axis=-1
    )
    gate = jnp.take_along_axis(prob, chosen[:, None], axis=-1)[:, 0]
    return chosen, gate, z


def experts(h, p, z, spec, cast):
    """Every expert held applied to every token, selected by the choice:
    a choice past ``num_experts`` equals no expert's id."""
    chosen, gate, z = route(h, p, z, spec, cast)

    def one_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        g = jnp.where(
            (chosen == spec["first_expert"] + e)
            & (chosen < spec["num_experts"]), gate, 0.0,
        )
        hidden = jax.nn.silu(dot(h, w_gate, cast)) * dot(h, w_up, cast)
        return y + g[:, None] * dot(hidden, w_down, cast), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]),
    )
    return y, z


def merge(x, y, s):
    return s["a_r"] * (x + s["b_r"]) + s["a_y"] * (y + s["b_y"])


def block(x, z, bp, seg, spec, cast):
    eps = spec["eps"]
    y = attention(rms(x, bp["norm1"]["scale"], eps), bp["attn"], seg, spec,
                  cast)
    x = merge(x, y, bp["scale_attn"])
    y, z = experts(rms(x, bp["norm2"]["scale"], eps), bp["moe"], z, spec,
                   cast)
    return merge(x, y, bp["scale_mlp"]), z


def trunk(p, tokens, seg, spec, cast):
    """tokens [T] -> the last block's output [T, d] and the router's state
    after it [T, 256]."""
    x = p["embed"]["embedding"][tokens]
    bp = p["block_0"]
    z = jnp.zeros((x.shape[0], bp["moe"]["router_gamma"].shape[-1]), x.dtype)
    one = jax.checkpoint(
        lambda carry, bp: block(*carry, bp, seg, spec, cast)
    )
    (x, z), _ = jax.lax.scan(lambda c, bp: (one(c, bp), None), (x, z), bp)
    return x, z


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


def value(x, p, cast):
    return dot(x, p["baseline"]["kernel"], cast)[:, 0] + p["baseline"][
        "bias"
    ][0]


def column_terms(p, chunk, c, spec, cast):
    """One packed sequence's sums: what the losses are means of."""
    tokens = chunk["obs"][:, c].astype(jnp.int32)
    seg = jnp.cumsum(chunk["done"][:, c].astype(jnp.int32))
    T1 = tokens.shape[0]
    h, _ = trunk(p, tokens, seg, spec, cast)
    x = rms(h, p["final_norm"]["scale"], spec["eps"])
    head = p["embed"]["embedding"].T  # tied
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
        "entropy": jnp.sum(entropy), "baseline": value(x, p, cast),
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
        pg = critic = entropy = 0.0
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
            critic = critic + 0.5 * jnp.sum((vs - values) ** 2)
            entropy = entropy + t["entropy"]
        total = (
            pg + loss["baseline_cost"] * critic
            - loss["entropy_cost"] * entropy
        ) / denom
        zero = jnp.zeros((), jnp.float32)
        return total, {"mtp_loss": zero, "mtp_positions": zero}

    return loss_fn


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """[T, b] token ids -> logits [T, b, V], baseline [T, b] and the
        state handed on (none: ``()``), whole: for the tests' small
        sizes."""
        p = params["params"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        logits, baseline = [], []
        for c in range(obs.shape[1]):
            h, _ = trunk(p, obs[:, c].astype(jnp.int32), seg[:, c], spec, cast)
            x = rms(h, p["final_norm"]["scale"], spec["eps"])
            logits.append(dot(x, p["embed"]["embedding"].T, cast))
            baseline.append(value(x, p, cast))
        return jnp.stack(logits, axis=1), jnp.stack(baseline, axis=1), ()

    return forward


forward = make_forward(PUBLISHED)
loss_fn = make_loss(PUBLISHED)
