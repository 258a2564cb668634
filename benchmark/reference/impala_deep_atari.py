"""Plain reference of ``impala_deep_atari``: the deep network of Espeholt et
al. 2018 (Figure 3, right) without the LSTM, float32, from the description:

    x = frames / 255
    three sections at 16, 32, 32 channels, each
        x = conv3x3(x); x = maxpool3x3/2(x)
        twice:  x = x + conv3x3(relu(conv3x3(relu(x))))
    x = relu(x); x = relu(dense256(flatten(x)))
    policy logits = dense(x); baseline = dense1(x)

All convolutions pad to keep the size; the pool pads like them (so 84 ->
42 -> 21 -> 11). Parameters are read by name out of the tree the benchmark
seeded. ``cast`` rounds the operands of every convolution and product
(identity for the reference proper; see ``lib/reference_train.py``).
"""

import jax
import jax.numpy as jnp


def conv(x, p, cast, stride=1):
    y = jax.lax.conv_general_dilated(
        cast(x), cast(p["kernel"]), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y + p["bias"]


def dense(x, p, cast):
    return cast(x) @ cast(p["kernel"]) + p["bias"]


def maxpool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def forward(params, obs, done, core_state, cast):
    """[T, b, 84, 84, 4] uint8 -> logits [T, b, A], baseline [T, b]."""
    del done  # no recurrence
    p = params["params"]
    T, b = obs.shape[:2]
    x = obs.astype(jnp.float32).reshape((T * b,) + obs.shape[2:]) / 255.0
    for i in range(3):
        s = p[f"ConvSequence_{i}"]
        x = maxpool(conv(x, s["Conv_0"], cast))
        for j in range(2):
            r = s[f"ResidualBlock_{j}"]
            y = conv(jax.nn.relu(x), r["Conv_0"], cast)
            x = x + conv(jax.nn.relu(y), r["Conv_1"], cast)
    x = jax.nn.relu(x).reshape(T * b, -1)
    x = jax.nn.relu(dense(x, p["Dense_0"], cast))
    logits = dense(x, p["Dense_1"], cast).reshape(T, b, -1)
    baseline = dense(x, p["Dense_2"], cast).reshape(T, b)
    return logits, baseline, core_state
