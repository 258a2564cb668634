"""Seeded weights and batches for a decoder whose blocks mix tokens by the
gated delta rule and carry a state from call to call, over the tree
``lib/seeded_latent.py`` makes (stacked blocks, correction biases). What
that file does not know and would seed otherwise, at the scales the
configuration states under ``seeding``:

- the convolutions' taps ``conv_q`` / ``conv_k`` / ``conv_v`` at N(0,
  ``conv_scale``^2), ``taps^-1/2``: a projected row has unit variance at
  these weights, so the mixed row has too (at that file's 0.05 the value
  would be a hundredth of a key and the state a rounding error);
- ``A_log`` at N(``a_log_mean``, ``a_log_scale``^2) and ``dt_bias`` at
  N(``dt_bias_mean``, ``dt_bias_scale``^2): the log-decay of a channel is
  ``-exp(A_log) softplus(N(0, 1) + dt_bias)`` a position, and with the
  mean well below 0 and a wide spread the channels' time scales run from
  a few positions to hundreds, as a trained layer's do (the published
  initialisation draws the step log-uniformly over two decades); the
  spread's upper tail is what ``kda_log_decay_min`` reads, sums of ``g``
  over a chunk that a product of ``exp(G)`` and ``exp(-G)`` could not
  hold;

and the batch's ``core_state``, which no other decoder cell has: the
rule's state at N(0, ``state_scale``^2) a block and head and the rows
before the convolutions at N(0, ``rows_scale``^2), so that the first
episode of every sequence continues one the actor began. The episode
boundaries are drawn for the softmax layers' count of tiles alone (the
delta rule's work does not depend on where they fall) and with ``done[0]``
false; everything else of the batch is ``lib/seeded_lm.py``'s.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded_latent, seeded_lm
from .seeded import key_from_seed

param_shapes = seeded_lm.param_shapes


def make_params(shapes, seed: int, model: dict, seeding: dict):
    """``seeded_latent.make_params`` with the delta rule's own leaves
    written over, in one jitted call whose key is an argument."""
    params = seeded_latent.make_params(
        shapes, seed, model, seeding["correction_bias_scale"]
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    conv = (0.0, seeding["conv_scale"])
    draws = {
        "['conv_q']": conv, "['conv_k']": conv, "['conv_v']": conv,
        "['A_log']": (seeding["a_log_mean"], seeding["a_log_scale"]),
        "['dt_bias']": (seeding["dt_bias_mean"], seeding["dt_bias_scale"]),
    }
    own = {}
    for i, (path, leaf) in enumerate(leaves):
        for ending, draw in draws.items():
            if jax.tree_util.keystr(path).endswith(ending):
                own[i] = (jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), draw)

    def make(key):
        key = jax.random.fold_in(key, 5)
        return {
            i: (mean + scale * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32
            )).astype(leaf.dtype)
            for i, (leaf, (mean, scale)) in own.items()
        }

    # only these leaves pass through the program: the rest of the tree
    # (gigabytes at the benchmark's size) is handed on as it is
    made = jax.jit(make)(key_from_seed(seed))
    return jax.tree_util.tree_unflatten(treedef, [
        made.get(i, leaf) for i, (_, leaf) in enumerate(leaves)
    ])


def softmax_layers(model: dict) -> dict:
    """``model`` with the blocks that run the attention kernels alone,
    every repeat written out: what ``seeded_lm.attention_tiles`` counts."""
    model = seeded_latent.expanded_model(model)
    return dict(model, layers=[
        l for l in model["layers"]
        if not model["attention_kinds"][l["attention"]].get("delta")
    ])


def draw_done(seed: int, shape, done_rate: float, model: dict,
              tiles) -> np.ndarray:
    """``seeded_lm.draw_done`` (the same draws: ``[seed, 2, j]``) held to
    the softmax layers' count of tiles and to a first position that
    continues the state handed in."""
    counted = softmax_layers(model)
    for j in itertools.count():
        done = np.random.default_rng([seed, 2, j]).random(shape) < done_rate
        if not done[0].any() and (
            tiles is None
            or seeded_lm.attention_tiles(done, counted) == tiles
        ):
            return done
        if j == 20000:
            raise ValueError(
                f"no draw of {j} at rate {done_rate} leaves {tiles} tiles"
            )


def make_state(seed: int, shapes, seeding: dict):
    """The ``core_state`` of one batch: ``shapes`` is the program's
    ``initial_state`` (two leaves a stateful entry: the rule's state, then
    the rows before the convolutions)."""
    scales = (seeding["state_scale"], seeding["rows_scale"])

    def make(key):
        key = jax.random.fold_in(key, 6)
        return tuple(
            scales[i % 2] * jax.random.normal(
                jax.random.fold_in(key, i), s.shape, jnp.float32
            ) for i, s in enumerate(shapes)
        )

    return jax.jit(make)(key_from_seed(seed))


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, state_shapes,
                     tiles=None):
    """``seeded_lm.make_learn_batch`` with ``done`` drawn by this file's
    rule and the seeded ``core_state``; nothing else of the batch depends
    on either."""
    batch = seeded_lm.make_learn_batch(
        seed, config, unroll_length, batch_size, done_rate
    )
    done = draw_done(
        seed, (unroll_length + 1, batch_size), done_rate,
        config["model"]["kwargs"], tiles,
    )
    return dict(
        batch, done=jnp.asarray(done),
        core_state=make_state(seed, state_shapes, config["seeding"]),
    )
