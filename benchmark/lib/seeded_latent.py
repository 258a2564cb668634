"""Seeded weights and batches for a decoder whose parameter tree
``lib/seeded_lm.py`` cannot read: blocks stacked on a leading axis (a
repeated layer entry runs as a scan), a multi-token-prediction module
after the stack, and a correction bias beside each router.

Everything that can be is ``seeded_lm``'s: the scales of a leaf (its
``_leaf_value``, applied to one block of a stack at a time, so that a
stacked kernel's fan-in is one block's), the Zipf ids, the boundaries
drawn for a tile count, the subset search of the labelling. What is new:

- ``e_score_correction_bias`` is seeded N(0, ``bias_scale``^2), not zero:
  a program that leaves it out of the selection then routes differently
  (the configuration states the scale and the tests count the
  assignments it moves);
- the expert layers are found in the order they run (a stack's blocks one
  after the other, the prediction module's last), and the labelling of the
  experts held (``seeded_lm``'s docstring) permutes each router's columns
  and its bias's entries together.
"""

from __future__ import annotations

import jax
import numpy as np

from . import seeded_lm
from .seeded import key_from_seed

param_shapes = seeded_lm.param_shapes


def expanded_model(model: dict) -> dict:
    """``model`` (a configuration's ``model.kwargs``) with every repeated
    layer entry written out and the prediction module's block last: the
    blocks a forward pass runs, for whoever counts by block."""
    layers = [
        {"attention": l["attention"], "mlp": l["mlp"]}
        for l in model["layers"] for _ in range(l.get("repeat", 1))
    ]
    if model.get("mtp"):
        layers.append(dict(model["mtp"]))
    return dict(model, layers=layers)


def _stack_of(path: str, model: dict) -> int:
    """How many blocks the leaf at ``path`` stacks (1: none)."""
    for i, layer in enumerate(model["layers"]):
        if f"['block_{i}']" in path and "['mtp']" not in path:
            return layer.get("repeat", 1)
    return 1


def make_params(shapes, seed: int, model: dict, bias_scale: float):
    """Fill the pytree of ``ShapeDtypeStruct`` with seeded values, in one
    jitted call whose key is an argument."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def value(key, path, shape, dtype):
        if path.endswith("e_score_correction_bias']"):
            return (
                jax.random.normal(key, shape, np.float32) * bias_scale
            ).astype(dtype)
        return seeded_lm._leaf_value(key, path, shape, dtype)

    def make(key):
        key = jax.random.fold_in(key, 1)
        out = []
        for i, (path, leaf) in enumerate(leaves):
            path, k = jax.tree_util.keystr(path), jax.random.fold_in(key, i)
            n = _stack_of(path, model)
            if n == 1:
                out.append(value(k, path, leaf.shape, leaf.dtype))
            else:
                out.append(jax.vmap(
                    lambda kk: value(kk, path, leaf.shape[1:], leaf.dtype)
                )(jax.random.split(k, n)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(make)(key_from_seed(seed))


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, tiles=None):
    """``seeded_lm.make_learn_batch`` with the tiles counted over every
    block the step runs (the stack's repeats and the prediction
    module's)."""
    counted = dict(
        config, model={"kwargs": expanded_model(config["model"]["kwargs"])}
    )
    return seeded_lm.make_learn_batch(
        seed, counted, unroll_length, batch_size, done_rate, tiles=tiles
    )


def expert_layers(params) -> list:
    """``(names, index)`` of every expert layer in the order the layers
    run: ``names`` the path of its ``moe`` under ``params["params"]``,
    ``index`` its place in a stack (None: not stacked)."""
    p = params["params"]
    out = []
    names = sorted(
        (k for k in p if k.startswith("block_")), key=lambda k: int(k[6:])
    )
    for name in names:
        if "moe" not in p[name]:
            continue
        router = p[name]["moe"]["router"]
        if router.ndim == 3:
            out += [((name, "moe"), j) for j in range(router.shape[0])]
        else:
            out.append(((name, "moe"), None))
    if "mtp" in p and "moe" in p["mtp"]["block"]:
        out.append((("mtp", "block", "moe"), None))
    return out


def permute_routers(params, perms):
    """``params`` with expert layer ``l``'s router columns, and its
    correction bias's entries, taken in the order ``perms[l]`` (None: as
    they are)."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # new containers
    for (names, j), perm in zip(expert_layers(params), perms):
        if perm is None:
            continue
        moe = params["params"]
        for name in names:
            moe = moe[name]
        for leaf in ("router", "e_score_correction_bias"):
            if leaf in moe:
                x = moe[leaf]
                moe[leaf] = (
                    x[..., perm] if j is None
                    else x.at[j].set(x[j][..., perm])
                )
    return params


def balance_held(params, loads_fn, batch, held, seed: int):
    """``seeded_lm.balance_held`` over :func:`expert_layers`: layer ``l``
    is settled on pass ``l`` with the layers before it already settled.
    Returns ``(params, perms, held_before, held_after)``."""
    first, count = held
    perms, before, after = [], [], []
    for l in range(len(expert_layers(params))):
        loads = np.asarray(loads_fn(params, batch["obs"], batch["done"]))[l]
        perm = seeded_lm.held_first(
            loads, held, np.random.default_rng([seed, l])
        )
        perms.append(perm)
        before.append(int(loads[first:first + count].sum()))
        after.append(int(loads[perm][first:first + count].sum()))
        params = permute_routers(params, [None] * l + [perm])
    return params, perms, before, after
