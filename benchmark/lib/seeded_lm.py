"""Weights and batches from ``--seed`` for a language model that learns as
a token-level policy: what ``lib/seeded.py`` does for the pixel and glyph
configurations, for token observations.

- the parameter tree's *shapes* are the program's (``jax.eval_shape`` of its
  ``init`` on a token observation); every value comes from here;
- leaves: a ``kernel`` and the ``router`` at variance 1/fan-in; an expert
  leaf ``[experts, in, out]`` at variance 1/``in``, the fan-in of ONE
  expert (the expert axis is a batch of matrices; ``lib/seeded.py`` would
  take it for fan-in and seed every expert ``sqrt(experts)`` too small);
  a norm's ``scale`` near 1 and not 1, a bias small and not 0, so that a
  dropped one shows in the comparison;
- embedding rows at unit variance an element, NOT ``lib/seeded.py``'s
  1/sqrt(width). The first thing a block does to the stream is an RMS norm,
  so the rows' scale only sets how the token stands against what the
  blocks add to it. At 1/sqrt(width) an attention output (an average of
  hundreds of value rows, all but the same at every position) is fifty
  times the token; every position's stream is then one common vector,
  every token picks the same eight experts, and one expert of a layer is
  sent all 8,192 tokens or none (a CPU run at the benchmark's size: 877 to
  20,958 assignments held a layer against a mean of 8,192). A trained
  model's stream is its token first and context second; at unit variance
  the seeded one is too, and routing follows the token, uneven as Zipf
  makes it;
- observations: token ids drawn Zipf(s) over the vocabulary held, rank =
  id, so a few ids carry much of the sequence and routing is uneven as text
  makes it; ``actions[t] = obs[t + 1]``: the action is the next token;
- episode boundaries at the cell's rate, and, where the cell states its
  ``attention_tiles``, drawn again until the boundaries leave the flash
  kernels that many tiles to visit (:func:`draw_done`): a tile wholly in
  another episode is skipped, so the boundaries' places set the attention's
  work (272 to 542 tiles at this cell's size, 1.7 ms of a 172 ms step
  between two seeds, my chip runs, PR 26), and the benchmark's check asks
  that every seed do the same work. The draw is still the rate's: one of
  its commonest outcomes;
- which of the router's experts this chip holds is a labelling, and
  :func:`balance_held` chooses it: a seeded router is not a trained one,
  and with Zipf ids the eight experts that happen to be numbered 0-7 were
  sent anything from 0.7 to 1.25 of the host's mean load, seed by seed (the
  step's time with them: a spread of 8% over seeds, my chip runs, PR 26),
  where a trained router's balance gives every chip of the host its
  eighth. Each layer's router columns are reordered, layer by layer from
  the first, so that the experts held carry the mean load at the seeded
  weights on the seed's own tokens; how that load is dealt among them
  stays as uneven as the seed made it. A column permutation of a Gaussian
  matrix is as Gaussian as the matrix was.
"""

from __future__ import annotations

import itertools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

from . import counts_lm
from .seeded import key_from_seed

EXPERT_LEAVES = ("w_gate']", "w_up']", "w_down']")


def param_shapes(net):
    """The shapes of the program's parameter tree; no value is taken."""
    return jax.eval_shape(
        net.init, jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, 1), jnp.bool_), net.initial_state(1),
    )


def _leaf_value(key, path: str, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    if path.endswith(EXPERT_LEAVES):
        x = x / math.sqrt(shape[1])
    elif path.endswith(("kernel']", "router']")):
        x = x / math.sqrt(max(1, math.prod(shape[:-1])))
    elif path.endswith("embedding']"):
        pass  # unit variance: see the module's docstring
    elif path.endswith("scale']"):
        x = 1.0 + 0.05 * x
    else:
        x = x * 0.05
    return x.astype(dtype)


def make_params(shapes, seed: int, sharding=None):
    """Fill the pytree of ``ShapeDtypeStruct`` with seeded values, in one
    jitted call whose key is an argument: one program serves every seed."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

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


def zipf_tokens(key, shape, vocab: int, s: float):
    """Token ids with P(id = r) proportional to 1 / (r + 1)^s, by the
    inverse of the cumulative distribution."""
    weights = 1.0 / jnp.arange(1, vocab + 1, dtype=jnp.float32) ** s
    cdf = jnp.cumsum(weights) / jnp.sum(weights)
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)


def attention_tiles(done: np.ndarray, model: dict) -> int:
    """Tiles the flash kernels visit in one forward pass over ``done``
    [T+1, B], all layers of ``model`` (a configuration's ``model.kwargs``)
    and all columns: ``counts_lm.visible_tiles``, the kernels' own rule."""
    kinds, block = model["attention_kinds"], model["attention_block"]
    return sum(
        counts_lm.visible_tiles(
            counts_lm.segments(done[:, b]), block,
            kinds[layer["attention"]]["window"],
        )
        for b in range(done.shape[1]) for layer in model["layers"]
    )


def draw_done(seed: int, shape, done_rate: float, model: dict,
              tiles) -> np.ndarray:
    """``done`` [T+1, B], each frame a boundary with probability
    ``done_rate``; with ``tiles`` a number, the first of the seed's draws
    that leaves the attention that many tiles (see the module's
    docstring). On the host: a draw is counted before it is used."""
    for j in itertools.count():
        done = np.random.default_rng([seed, 2, j]).random(shape) < done_rate
        if tiles is None or attention_tiles(done, model) == tiles:
            return done
        if j == 20000:
            raise ValueError(
                f"no draw of {j} at rate {done_rate} leaves {tiles} tiles"
            )


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, sharding=None,
                     tiles=None):
    """The learn-batch contract of ``impala_loss`` (time-major [T+1, B])
    over token observations, made in one jitted call but for ``done``
    (:func:`draw_done`, on the host). Rewards and behaviour logits as
    ``lib/seeded.py`` makes them."""
    T, B = unroll_length, batch_size
    A = config["num_actions"]
    spec = config["observation"]
    if spec["vocab"] != A:
        raise ValueError("the action is the next token: one vocabulary")
    done = draw_done(
        seed, (T + 1, B), done_rate,
        None if tiles is None else config["model"]["kwargs"], tiles,
    )

    def make(key, done):
        ks = jax.random.split(jax.random.fold_in(key, 2), 4)
        obs = zipf_tokens(ks[0], (T + 1, B), spec["vocab"], spec["zipf_s"])
        return {
            "obs": obs,
            "done": done,
            "rewards": jnp.abs(
                jax.random.normal(ks[2], (T + 1, B), jnp.float32)
            ) * jnp.linspace(0.1, 1.0, B, dtype=jnp.float32),
            "actions": obs[1:],
            "behavior_logits": jax.random.normal(
                ks[3], (T, B, A), jnp.float32
            ),
            "core_state": (),
        }

    return jax.jit(make, out_shardings=sharding)(key_from_seed(seed), done)


def nearest_subset(loads: np.ndarray, count: int, target: float,
                   rng: np.random.Generator) -> np.ndarray:
    """``count`` indices of ``loads`` whose sum comes nearest ``target``:
    the best of 4,096 drawn subsets, then single swaps for as long as one
    comes nearer. Ascending."""
    E = len(loads)
    drawn = np.argsort(rng.random((4096, E)), axis=1)[:, :count]
    best = drawn[np.argmin(np.abs(loads[drawn].sum(axis=1) - target))]
    while True:
        outside = np.setdiff1d(np.arange(E), best)
        gap = loads[best].sum() - target
        after = np.abs(gap + loads[outside][None, :] - loads[best][:, None])
        i, j = np.unravel_index(np.argmin(after), after.shape)
        if after[i, j] >= abs(gap):
            return np.sort(best)
        best = np.append(np.delete(best, i), outside[j])


def held_first(loads: np.ndarray, held, rng) -> np.ndarray:
    """The permutation of a router's columns that seats, at the ids held
    (``held = (first, count)``), the experts whose load together is nearest
    the mean share, ``count / E`` of all assignments; the others follow in
    their old order. Column ``j`` of the new router is column ``perm[j]``
    of the old."""
    first, count = held
    chosen = nearest_subset(
        loads, count, loads.sum() * count / len(loads), rng
    )
    rest = np.setdiff1d(np.arange(len(loads)), chosen)
    return np.concatenate([rest[:first], chosen, rest[first:]])


def _router_leaves(params) -> list:
    """Paths of the router leaves, in layer order."""
    paths = [
        path for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        if jax.tree_util.keystr(path).endswith("router']")
    ]
    return sorted(paths, key=lambda path: int(
        re.search(r"block_(\d+)", jax.tree_util.keystr(path)).group(1)
    ))


def permute_routers(params, perms):
    """``params`` with layer ``l``'s router columns taken in the order
    ``perms[l]`` (None: as they are)."""
    wanted = {
        jax.tree_util.keystr(path): perm
        for path, perm in zip(_router_leaves(params), perms)
        if perm is not None
    }
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf[:, wanted[jax.tree_util.keystr(path)]]
        if jax.tree_util.keystr(path) in wanted else leaf,
        params,
    )


def balance_held(params, loads_fn, batch, held, seed: int):
    """The labelling of the module's docstring, found on the device.
    ``loads_fn(params, obs, done) -> [layers, E]`` is the program's own
    count of each router's assignments. A layer's routing depends on the
    layers before it (the experts held add to the stream), so layer ``l``
    is settled on pass ``l``, with the layers before it already settled.
    Returns ``(params, perms, held_before, held_after)``, the two last the
    assignments held by layer with the columns as seeded and as
    reordered."""
    first, count = held
    perms, before, after = [], [], []
    layers = len(_router_leaves(params))
    for l in range(layers):
        loads = np.asarray(loads_fn(params, batch["obs"], batch["done"]))[l]
        perm = held_first(loads, held, np.random.default_rng([seed, l]))
        perms.append(perm)
        before.append(int(loads[first:first + count].sum()))
        after.append(int(loads[perm][first:first + count].sum()))
        params = permute_routers(params, [None] * l + [perm])
    return params, perms, before, after
