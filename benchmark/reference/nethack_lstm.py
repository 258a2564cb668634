"""Plain reference of ``nethack_lstm``, float32, from the description of
``models/nethack.py`` (the NLE baseline's shape at this repository's widths):

    g = embedding[glyphs]                       [21, 79, 16]
    three times: g = relu(conv3x3/2(g))         32, 64, 64 channels
    s = relu(dense64(tanh(blstats / 100)))
    x = relu(dense256([flatten(g), s]))
    LSTM 256 over time; where done_t is set, (c, h) are zeroed BEFORE step t:
        i, f, o = sigmoid(W_i* x + W_h* h + b),  g = tanh(...)
        c = f c + i g;  h = o tanh(c)
    policy logits = dense(h); baseline = dense1(h)

The input products of the LSTM have no bias, the recurrent ones have.
Parameters are read by name out of the tree the benchmark seeded.
"""

import jax
import jax.numpy as jnp

from .impala_deep_atari import conv, dense  # the same two plain operators


def lstm_step(p, cast, carry, xs):
    x, done = xs
    c, h = carry
    keep = 1.0 - done.astype(jnp.float32)[:, None]
    c, h = c * keep, h * keep

    def gate(name):
        return (
            cast(x) @ cast(p["i" + name]["kernel"])
            + cast(h) @ cast(p["h" + name]["kernel"])
            + p["h" + name]["bias"]
        )

    i, f, o = (jax.nn.sigmoid(gate(n)) for n in "ifo")
    c = f * c + i * jnp.tanh(gate("g"))
    h = o * jnp.tanh(c)
    return (c, h), h


def forward(params, obs, done, core_state, cast):
    p = params["params"]
    glyphs, blstats = obs["glyphs"], obs["blstats"]
    T, b = glyphs.shape[:2]
    g = p["glyph_embed"]["embedding"][
        glyphs.astype(jnp.int32).reshape((T * b,) + glyphs.shape[2:])
    ]
    for i in range(3):
        g = jax.nn.relu(conv(g, p[f"Conv_{i}"], cast, stride=2))
    s = jnp.tanh(blstats.astype(jnp.float32).reshape(T * b, -1) * 0.01)
    s = jax.nn.relu(dense(s, p["Dense_0"], cast))
    x = jnp.concatenate([g.reshape(T * b, -1), s], axis=-1)
    x = jax.nn.relu(dense(x, p["Dense_1"], cast)).reshape(T, b, -1)
    cell = p["LSTMCore_0"]["Scan_MaskedLSTMStep_0"]["OptimizedLSTMCell_0"]
    core_state, x = jax.lax.scan(
        lambda carry, xs: lstm_step(cell, cast, carry, xs),
        tuple(core_state), (x, done),
    )
    logits = dense(x, p["policy"], cast)
    baseline = dense(x, p["baseline"], cast)[..., 0]
    return logits, baseline, core_state
