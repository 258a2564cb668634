"""Seeded values for the mixing parameters of a residual skeleton with
several streams (``hc_attn`` / ``hc_mlp`` under every block: ``phi``
[n d, n^2 + 2n], ``b`` [n^2 + 2n], ``alpha`` [3]), which
``lib/seeded_latent.py`` does not know and would seed as small biases.
Everything else of the tree is that file's, unedited: this one takes its
tree and writes these leaves over.

- ``phi`` at variance 1/(n d): the normalised streams have unit mean
  square, so every entry of ``m = xt phi`` is N(0, 1) and the sigmoids
  and the exponentials move with the token;
- ``alpha`` = 1;
- ``b`` at N(0, ``b_scale``^2), with ``res_diagonal`` added on the
  diagonal of ``mat(b[2n:])``: the remix matrix then leans to the
  identity as a trained one does (a stream mostly keeps itself) and is
  far from it and from uniform, so that a program that leaves out the
  Sinkhorn iterations, a gate's factor 2 or a sublayer's own ``b`` fails
  the comparison (``tests/test_lm_hyper.py`` counts that at a small
  size). The configuration states both numbers under ``seeding``.

A stacked leaf (a repeated layer entry) is drawn whole: its blocks differ
because the draw does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .seeded import key_from_seed

MIXERS = ("['hc_attn']", "['hc_mlp']")


def seed_mixing(params, seed: int, streams: int, b_scale: float,
                res_diagonal: float):
    """``params`` with every mixing leaf seeded as the module's docstring
    says, in one jitted call whose key is an argument."""
    n = streams
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    mixing = {
        i: (jax.tree_util.keystr(path), jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype))
        for i, (path, leaf) in enumerate(leaves)
        if any(m in jax.tree_util.keystr(path) for m in MIXERS)
    }

    def value(key, path, leaf):
        if path.endswith("['phi']"):
            return jax.random.normal(key, leaf.shape, jnp.float32) * (
                leaf.shape[-2] ** -0.5
            )
        if path.endswith("['alpha']"):
            return jnp.ones(leaf.shape, jnp.float32)
        assert path.endswith("['b']"), path
        b = jax.random.normal(key, leaf.shape, jnp.float32) * b_scale
        return b.at[..., 2 * n:].add(res_diagonal * jnp.eye(n).reshape(-1))

    def make(key):
        key = jax.random.fold_in(key, 3)
        return {
            i: value(jax.random.fold_in(key, i), path, leaf).astype(
                leaf.dtype)
            for i, (path, leaf) in mixing.items()
        }

    # only the mixing's leaves pass through the program: the rest of the
    # tree (gigabytes at the benchmark's size) is handed on as it is
    made = jax.jit(make)(key_from_seed(seed))
    return jax.tree_util.tree_unflatten(treedef, [
        made.get(i, leaf) for i, (_, leaf) in enumerate(leaves)
    ])
