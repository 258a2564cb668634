"""Weights and batches from ``--seed``, made on the device they live on.

The program supplies only the *shapes* of its parameter tree
(``jax.eval_shape`` of its ``init``); every value comes from here, so the
program and the plain reference are handed the same numbers and neither
takes anything the other made. One jitted call makes the whole tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key_from_seed(seed: int) -> jax.Array:
    """A key for any whole-number seed: the low 31 bits make the key, the
    rest is folded in, so seeds past 2**31 stay distinct."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def _leaf_value(key, path: str, shape, dtype):
    """Kernels: normal with variance 1/fan-in (every axis but the last), the
    scale the program's own initialisers draw at, so that logits and values
    start small as they do in a real run. Embedding rows: 1/sqrt(width).
    Biases: small but NOT zero, so a dropped bias shows in the
    comparison."""
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("kernel']"):
        x = x / math.sqrt(max(1, math.prod(shape[:-1])))
    elif path.endswith("embedding']"):
        x = x / math.sqrt(shape[-1])
    else:
        x = x * 0.05
    return x.astype(dtype)


def make_params(shapes, seed: int, sharding=None):
    """Fill the pytree of ``ShapeDtypeStruct`` with seeded values, in one
    jitted call, on ``sharding`` (default: the default device)."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    # The key is an argument, not a constant of the program: one program
    # serves every seed, and the compile cache finds it again.
    def make(key):
        key = jax.random.fold_in(key, 1)
        return jax.tree_util.tree_unflatten(
            treedef,
            [
                _leaf_value(
                    jax.random.fold_in(key, i),
                    jax.tree_util.keystr(path), leaf.shape, leaf.dtype,
                )
                for i, (path, leaf) in enumerate(leaves)
            ],
        )

    return jax.jit(make, out_shardings=sharding)(key_from_seed(seed))


def _pixels(key, lead, spec):
    return jax.random.bits(key, lead + tuple(spec["shape"]), jnp.uint8)


def _nethack(key, lead, spec):
    kg, kb = jax.random.split(key)
    return {
        "glyphs": jax.random.randint(
            kg, lead + tuple(spec["glyphs_shape"]), 0, spec["num_glyphs"],
            jnp.int32,
        ).astype(jnp.int16),
        # blstats are counters (hit points, gold, turns): non-negative,
        # hundreds, squashed by the model itself.
        "blstats": jax.random.uniform(
            kb, lead + (spec["blstats_size"],), jnp.float32, 0.0, 500.0
        ),
    }


OBSERVATIONS = {"pixels": _pixels, "nethack": _nethack}


def observation(key, lead, spec):
    """One observation pytree with leading axes ``lead``, by the
    configuration's ``observation.kind``."""
    return OBSERVATIONS[spec["kind"]](key, tuple(lead), spec)


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, sharding=None):
    """The learn-batch contract of ``impala_loss`` (time-major [T+1, B]),
    every column different, made in one jitted call. ``sharding`` is a
    pytree-prefix of shardings for the batch dict, or None."""
    T, B = unroll_length, batch_size
    A = config["num_actions"]
    core = config.get("core_state_size", 0)

    def make(key):
        ks = jax.random.split(jax.random.fold_in(key, 2), 7)
        batch = {
            "obs": observation(ks[0], (T + 1, B), config["observation"]),
            "done": jax.random.uniform(ks[1], (T + 1, B)) < done_rate,
            # Rewards are positive, so returns and advantages have a sign
            # in common and the gradient is a signal, not the residue of
            # terms that cancel (on zero-mean rewards rounding alone moves
            # it by tens of percent). Their scale rises from column to
            # column (0.1 to 1.0): the columns are not alike, so a part of
            # the batch left out or weighted wrongly moves the loss by far
            # more than rounding does.
            "rewards": jnp.abs(
                jax.random.normal(ks[2], (T + 1, B), jnp.float32)
            ) * jnp.linspace(0.1, 1.0, B, dtype=jnp.float32),
            "actions": jax.random.randint(ks[3], (T, B), 0, A, jnp.int32),
            "behavior_logits": jax.random.normal(
                ks[4], (T, B, A), jnp.float32
            ),
            "core_state": (),
        }
        if core:
            # A non-zero state: an unroll that starts mid-episode.
            batch["core_state"] = (
                0.5 * jax.random.normal(ks[5], (B, core), jnp.float32),
                0.5 * jax.random.normal(ks[6], (B, core), jnp.float32),
            )
        return batch

    return jax.jit(make, out_shardings=sharding)(key_from_seed(seed))
