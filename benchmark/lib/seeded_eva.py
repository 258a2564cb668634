"""Seeded weights and batches for a decoder with chunk-summary attention,
RMS norms with a unit offset and no expert layer, over the tree
``lib/seeded_latent.py`` makes (stacked blocks: a stacked kernel's fan-in
is one block's). What that file does not know and would seed otherwise:

- a norm's ``scale`` is the gain's distance from 1 here
  (``norm_add_unit_offset``): N(0, ``norm_offset_scale``^2), near 0 and
  not 0, where that file seeds a gain near 1 (read as an offset that would
  double every norm's output);
- ``phi`` at N(0, ``phi_scale``^2): a key after the rotary has unit
  variance an element at these weights, so a chunk's pooling scores ``k .
  phi d^-1/2`` are N(0, ``phi_scale``^2) and its 16 weights move with the
  bytes (at 0 the pooling would be a plain mean and ``phi`` would take no
  part in the forward pass);
- ``mu`` at N(0, ``mu_scale``^2), the size of a pooled key's elements: a
  program that drops it, or adds it to the value, fails the comparison;

the configuration states the three numbers under ``seeding``. The episode
boundaries are drawn for a count of the attention's tiles, local and
summary together (``lib/counts_eva.py``), as ``lib/seeded_lm.py`` draws
them for its kernels' count, and with at least one boundary in every
column; everything else of the batch is that file's.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import counts_eva, seeded_latent, seeded_lm
from .seeded import key_from_seed

param_shapes = seeded_lm.param_shapes


def make_params(shapes, seed: int, model: dict, seeding: dict):
    """``seeded_latent.make_params`` with the norms' offsets, ``phi`` and
    ``mu`` written over, in one jitted call whose key is an argument."""
    params = seeded_latent.make_params(shapes, seed, model, 0.0)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    scales = {
        "['scale']": seeding["norm_offset_scale"],
        "['phi']": seeding["phi_scale"], "['mu']": seeding["mu_scale"],
    }
    own = {}
    for i, (path, leaf) in enumerate(leaves):
        for ending, scale in scales.items():
            if jax.tree_util.keystr(path).endswith(ending):
                own[i] = (jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), scale)

    def make(key):
        key = jax.random.fold_in(key, 4)
        return {
            i: (jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape, jnp.float32
            ) * scale).astype(leaf.dtype)
            for i, (leaf, scale) in own.items()
        }

    # only these leaves pass through the program: the rest of the tree
    # (gigabytes at the benchmark's size) is handed on as it is
    made = jax.jit(make)(key_from_seed(seed))
    return jax.tree_util.tree_unflatten(treedef, [
        made.get(i, leaf) for i, (_, leaf) in enumerate(leaves)
    ])


def draw_done(seed: int, shape, done_rate: float, model: dict,
              tiles) -> np.ndarray:
    """``seeded_lm.draw_done`` (the same draws: ``[seed, 2, j]``) held to
    this attention's count of tiles, and to at least one boundary inside
    every column: the episode rule of the summaries, the chunks that
    straddle and the tiles an episode hides are then in every seed's step
    (of the draws with every tile there is, most hold no boundary)."""
    for j in itertools.count():
        done = np.random.default_rng([seed, 2, j]).random(shape) < done_rate
        if tiles is None or (
            done[1:].any(axis=0).all()
            and counts_eva.visible_tiles(done, model) == tiles
        ):
            return done
        if j == 20000:
            raise ValueError(
                f"no draw of {j} at rate {done_rate} leaves {tiles} tiles "
                "and a boundary"
            )


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, tiles=None):
    """``seeded_lm.make_learn_batch`` with ``done`` drawn by this file's
    count; nothing else of the batch depends on it."""
    batch = seeded_lm.make_learn_batch(
        seed, config, unroll_length, batch_size, done_rate
    )
    done = draw_done(
        seed, (unroll_length + 1, batch_size), done_rate,
        config["model"]["kwargs"], tiles,
    )
    return dict(batch, done=jnp.asarray(done))
