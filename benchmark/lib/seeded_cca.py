"""Seeded weights and batches for a decoder of compressed convolutional
attention and top-1 experts behind an MLP router, over the tree
``lib/seeded_latent.py`` makes (stacked blocks, kernels at variance 1 /
fan-in, norm gains near 1, everything else N(0, 0.05^2)). What that file
does not know and would seed otherwise, at the scales the configuration
states under ``seeding``:

- ``conv0`` (depthwise taps) at N(0, ``conv0_scale``^2) and ``conv1``
  (a ``D x D`` matrix a tap and head) at N(0, ``conv1_gain``^2 / (taps
  D)): a mixed row then stands beside the query-key mean it is added to,
  neither a rounding error of the other;
- ``temperature``, ``router_gamma`` and the residual scales ``a_r`` /
  ``a_y`` at 1 + N(0, ``unit_scale``^2): near what they start at and not
  at it, so that a dropped one shows (the shifts ``b_r`` / ``b_y`` and the
  biases keep that file's N(0, 0.05^2), near 0 and not at it);
- the final norm's gain at ``head_gain`` (1 + N(0, ``unit_scale``^2)),
  ``head_gain`` the hidden width's ``-1/2`` power. The head is the
  embedding, whose rows have unit variance an element for the stream's
  sake (``lib/seeded_lm.py``): behind a gain of 1 the logits' spread would
  be ``sqrt(width)``, 45 at 2,048, the policy one token, every importance
  ratio zero, and the policy-gradient and entropy terms, the only ones
  that reach the head, nothing (the cell's first chip run read a total
  loss of 1.3e-4 where the other cells read 2: PERF.md, PR 46). A trained
  tied model's last gain carries that scale; so does this one;
- the router's last product ``router_out`` at ``router_out_gain`` times
  its 1 / fan-in: the logits then spread enough that the 17 choices are
  uneven, as a trained router's are, and none is empty; the selection bias
  at N(0, ``correction_bias_scale``^2) as the sigmoid cells seed theirs.

Which of the router's experts this chip holds is a labelling
(``lib/seeded_lm.py``'s docstring), found here over the router's *expert*
columns alone: the last ``skip_choices`` columns are no expert and stay
where they are. The eight held are seated so that they carry ``count /
choices`` of all the tokens, the mean load of one of 17 choices times
eight. A layer's router is the last product's columns and the selection
bias's entries, permuted together.

The episode boundaries are ``lib/seeded_kda.py``'s draw: ``done[0]``
false, and the first of the seed's draws that leaves the attention
kernels the cell's count of tiles (a count below the whole triangle's, so
a draw that holds a boundary).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded_kda, seeded_latent, seeded_lm
from .seeded import key_from_seed

param_shapes = seeded_lm.param_shapes


def make_params(shapes, seed: int, model: dict, seeding: dict):
    """``seeded_latent.make_params`` with this model's own leaves written
    over, in one jitted call whose key is an argument."""
    params = seeded_latent.make_params(
        shapes, seed, model, seeding["correction_bias_scale"]
    )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    unit = (1.0, seeding["unit_scale"])

    def draw(name: str, shape):
        if name == "['attn']['conv0']":
            return 0.0, seeding["conv0_scale"]
        if name == "['attn']['conv1']":  # [blocks, taps, heads, D, D]
            return 0.0, seeding["conv1_gain"] / np.sqrt(shape[-4] * shape[-1])
        if name == "['router_out']['kernel']":
            return 0.0, seeding["router_out_gain"] / np.sqrt(shape[-2])
        if name.endswith(("['temperature']", "['router_gamma']", "['a_r']",
                          "['a_y']")):
            return unit
        if name == "['final_norm']['scale']":
            gain = seeding["head_gain"]
            return gain, gain * seeding["unit_scale"]
        return None

    own = {}
    for i, (path, leaf) in enumerate(leaves):
        found = draw(_tail(jax.tree_util.keystr(path)), leaf.shape)
        if found is not None:
            own[i] = (jax.ShapeDtypeStruct(leaf.shape, leaf.dtype), found)

    def make(key):
        key = jax.random.fold_in(key, 7)
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


def _tail(name: str) -> str:
    """A leaf's path from its module on: ``['attn']['conv0']``."""
    keys = name.split("][")
    return "[" + "][".join(keys[-2:]) if len(keys) > 1 else name


def _layers(params) -> list:
    """``(block name, index in its stack or None)`` of every expert layer
    in the order the layers run."""
    p = params["params"]
    names = sorted(
        (k for k in p if k.startswith("block_") and "moe" in p[k]),
        key=lambda k: int(k[6:]),
    )
    out = []
    for name in names:
        kernel = p[name]["moe"]["router_out"]["kernel"]
        out += [
            (name, j)
            for j in (range(kernel.shape[0]) if kernel.ndim == 3 else [None])
        ]
    return out


def permute_routers(params, perms):
    """``params`` with expert layer ``l``'s choices taken in the order
    ``perms[l]`` (None: as they are): the columns of its router's last
    product and the entries of its selection bias."""
    params = jax.tree_util.tree_map(lambda x: x, params)  # new containers
    for (name, j), perm in zip(_layers(params), perms):
        if perm is None:
            continue
        moe = params["params"][name]["moe"]

        def taken(x):
            return x[..., perm] if j is None else x.at[j].set(x[j][..., perm])

        moe["router_out"] = {"kernel": taken(moe["router_out"]["kernel"])}
        moe["e_score_correction_bias"] = taken(moe["e_score_correction_bias"])
    return params


def held_first(loads: np.ndarray, held, skip: int, rng) -> np.ndarray:
    """The order of a router's choices that seats, at the ids held, the
    experts whose load together is nearest ``count / choices`` of all the
    assignments; the other experts follow in their old order, and the last
    ``skip`` choices, no experts, stay last."""
    first, count = held
    experts = len(loads) - skip
    chosen = seeded_lm.nearest_subset(
        loads[:experts], count, loads.sum() * count / len(loads), rng
    )
    rest = np.setdiff1d(np.arange(experts), chosen)
    return np.concatenate(
        [rest[:first], chosen, rest[first:], np.arange(experts, len(loads))]
    )


def balance_held(params, loads_fn, batch, held, skip: int, seed: int):
    """``seeded_latent.balance_held`` over this file's routers: layer
    ``l`` is settled on pass ``l`` with the layers before it already
    settled. Returns ``(params, perms, held_before, held_after)``."""
    first, count = held
    perms, before, after = [], [], []
    for l in range(len(_layers(params))):
        loads = np.asarray(loads_fn(params, batch["obs"], batch["done"]))[l]
        perm = held_first(loads, held, skip, np.random.default_rng([seed, l]))
        perms.append(perm)
        before.append(int(loads[first:first + count].sum()))
        after.append(int(loads[perm][first:first + count].sum()))
        params = permute_routers(params, [None] * l + [perm])
    return params, perms, before, after


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, tiles=None):
    """``seeded_lm.make_learn_batch`` with ``done`` drawn by
    ``seeded_kda.draw_done``'s rule (``done[0]`` false, the tiles counted
    over every block); nothing else of the batch depends on it."""
    batch = seeded_lm.make_learn_batch(
        seed, config, unroll_length, batch_size, done_rate
    )
    done = seeded_kda.draw_done(
        seed, (unroll_length + 1, batch_size), done_rate,
        config["model"]["kwargs"], tiles,
    )
    return dict(batch, done=jnp.asarray(done))
