"""Plain reference of ``mellum2_share8``: one chip's share of a stage of
Mellum2-12B-A2.5B (JetBrains, ``config.json``, ``model_type`` mellum) as a
token-level actor-critic policy, float32 ``jax.numpy`` from the equations.
Imports nothing of the program.

x is [tokens, 2304]; one column of the batch is one packed sequence.

    rms(x) = x / sqrt(mean(x^2) + 1e-6) * w

    block l:
      z = rms1(x);  q = z Wq as H heads of 128;  k, v = z Wk, z Wv as Hkv
      heads of 128, query head h reading key/value head h // (H / Hkv)
      rotary on q and k over the whole head (half-split form),
        inv_freq_i = 500000^(-2i/128); on a full-attention layer the YaRN
        blend of it (`inv_freq` below) and cos, sin times attention_factor
      key j visible to query i iff j <= i, both in one episode segment
        (segment = running count of `done`), and, on a sliding layer,
        i - j < 1024
      h = x + softmax(q k^T / sqrt(128)) v, heads concatenated, times Wo
      z = rms2(h);  p = softmax(z Wr) over all 64;  S = the 8 largest
      g_e = p_e / sum_S p
      out = h + sum over e in S held here of
                g_e * (silu(z Wgate_e) * (z Wup_e)) Wdown_e

    logits = rms_f(x) Whead;  baseline = rms_f(x) wv + b

The share: ``H``, ``Hkv``, the experts held and the vocabulary rows are read
off the parameter shapes (4 and 1, 8 of a 64-wide router, 12,288 rows at
the benchmark's size); what the absent heads and experts would add is left
out, as in the program. The held experts are a loop (a scan) with a mask
over every token; scores are a dense matrix computed in blocks of query rows and
recomputed in the backward pass, as is each block, so that no [H, T, T]
array is ever held. ``cast`` rounds both operands of every matrix product
(identity for the reference proper; see ``lib/reference_train.py``).
"""

import math

import jax
import jax.numpy as jnp

# What the parameter shapes do not say: the published settings.
PUBLISHED = {
    "layer_types": ("sliding", "sliding", "sliding", "full") * 2,
    "head_dim": 128,
    "window": 1024,
    "top_k": 8,
    "first_expert": 0,  # the held experts are router ids first..first+count
    "eps": 1e-6,
    "theta": 500000.0,
    # rope_parameters.full_attention
    "yarn": {"factor": 16.0, "original": 8192, "beta_fast": 32.0,
             "beta_slow": 1.0, "attention_factor": 1.2772588722239782},
    "query_rows": 512,  # rows of the score matrix computed at a time
}


def inv_freq(spec, yarn: bool):
    d = spec["head_dim"]
    base = [spec["theta"] ** (-2.0 * i / d) for i in range(d // 2)]
    if not yarn:
        return jnp.asarray(base, jnp.float32)
    y = spec["yarn"]

    def dim(n):
        return d * math.log(y["original"] / (2 * math.pi * n)) / (
            2 * math.log(spec["theta"])
        )

    low = max(math.floor(dim(y["beta_fast"])), 0)
    high = min(math.ceil(dim(y["beta_slow"])), d - 1)
    out = []
    for i, f in enumerate(base):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / y["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotate(x, cos, sin):
    """x [T, heads, D]; cos, sin [T, D / 2]: pairs (i, i + D/2) turn by
    the angle of frequency i."""
    a, b = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def attention(z, p, seg, spec, sliding, cast):
    T = z.shape[0]
    D = spec["head_dim"]
    q = (cast(z) @ cast(p["q"]["kernel"])).reshape(T, -1, D)
    k = (cast(z) @ cast(p["k"]["kernel"])).reshape(T, -1, D)
    v = (cast(z) @ cast(p["v"]["kernel"])).reshape(T, -1, D)
    H, Hkv = q.shape[1], k.shape[1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq(
        spec, yarn=not sliding
    )
    factor = 1.0 if sliding else spec["yarn"]["attention_factor"]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    # each query head beside the key/value head it reads
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
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
        if sliding:
            seen = seen & (i[:, None] - j[None, :] < spec["window"])
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hij,jhd->ihd", cast(w), cast(v))

    o = jax.lax.map(block, jnp.arange(0, T, rows)).reshape(T, H * D)
    return cast(o) @ cast(p["o"]["kernel"])


def experts(z, p, spec, cast):
    probs = jax.nn.softmax(cast(z) @ cast(p["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, spec["top_k"])
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    def one_expert(y, expert):
        e, w_gate, w_up, w_down = expert
        # this expert's gate a token: 0 where the token did not choose it
        g = jnp.sum(
            jnp.where(top_i == spec["first_expert"] + e, gates, 0.0), axis=-1
        )
        hidden = jax.nn.silu(cast(z) @ cast(w_gate)) * (cast(z) @ cast(w_up))
        return y + g[:, None] * (cast(hidden) @ cast(w_down)), None

    # The loop over the experts held, every one over every token behind its
    # mask. A scan and not a Python loop, so that the compiler builds one
    # expert's program and not eight (unrolled, the reference's step was a
    # gigabyte of code on the chip).
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
         p["w_down"]),
    )
    return y


def column(p, tokens, seg, spec, cast):
    """One packed sequence: tokens [T] -> logits [T, V], baseline [T]."""
    eps = spec["eps"]
    x = p["embed"]["embedding"][tokens]

    def block(x, bp, sliding):
        h = x + attention(
            rms(x, bp["norm1"]["scale"], eps), bp["attn"], seg, spec,
            sliding, cast,
        )
        return h + experts(rms(h, bp["norm2"]["scale"], eps), bp["moe"],
                           spec, cast)

    for i, kind in enumerate(spec["layer_types"]):
        x = jax.checkpoint(block, static_argnums=2)(
            x, p[f"block_{i}"], kind == "sliding"
        )
    x = rms(x, p["final_norm"]["scale"], eps)
    logits = cast(x) @ cast(p["head"]["kernel"])
    baseline = (cast(x) @ cast(p["baseline"]["kernel"]))[:, 0] + p[
        "baseline"
    ]["bias"][0]
    return logits, baseline


def make_forward(spec):
    def forward(params, obs, done, core_state, cast):
        """[T, b] token ids -> logits [T, b, V], baseline [T, b]."""
        p = params["params"]
        seg = jnp.cumsum(done.astype(jnp.int32), axis=0)
        outs = [
            column(p, obs[:, c].astype(jnp.int32), seg[:, c], spec, cast)
            for c in range(obs.shape[1])
        ]
        logits = jnp.stack([o[0] for o in outs], axis=1)
        baseline = jnp.stack([o[1] for o in outs], axis=1)
        return logits, baseline, core_state

    return forward


forward = make_forward(PUBLISHED)
