"""Seeded weights and batches for a decoder under block diffusion whose
action is a denoising step, over the tree ``lib/seeded_latent.py`` makes
(stacked blocks, kernels and the router at variance 1 / fan-in, an
expert's matrices at 1 / fan-in of one expert, norm gains, the query/key
norm's among them, at 1 + 0.05 N(0,1): near 1 and not 1, so that a dropped
one shows; embedding rows, the MASK row among them, at unit variance an
element). The labelling of the experts held is that file's over all
``(1 + S) L`` rows the routers see (``lib/seeded_lm.py``'s docstring).

**The batch** (``impala_loss``'s contract with ``action_step``): one column
is one packed sequence of ``L = D (N + 1)`` tokens, ``N = unroll_length /
S`` acted blocks and the bootstrap frame's block.

- token ids Zipf(s) over the rows of the vocabulary held that are not the
  MASK row (rank r is id r below ``mask_id`` and r + 1 from it on);
- ``reveal_step`` [L, B]: of each acted block the first step reveals 1, 2
  or 3 of the four tokens with the probabilities the configuration states
  (``observation.first_step_reveals``: a confidence-ordered sampler's
  uneven steps) at positions drawn uniformly, the second step the rest;
  block ``N``'s tokens carry ``S`` (never revealed);
- ``action_step`` = ``S b(i) + r_i``; ``actions`` the tokens themselves
  (the action is the token revealed, not the next one); float32 N(0,1)
  ``behavior_logits`` ``[D N, B, A]``;
- ``rewards`` ``[S N + 1, B]`` as ``lib/seeded_lm.py`` draws them, on the
  step axis;
- ``done`` ``[S N + 1, B]`` on the step axis: a block's first step is a
  boundary with probability ``S x done_rate`` (the rate is a step's; a
  boundary lies at a block's first step alone), ``done[0]`` false, and,
  where the cell states its ``attention_tiles``, the first of the seed's
  draws that leaves the flash kernels that many tiles
  (``lib/counts_sdar.py:attention_tiles``: the kernels' own rule), so that
  every seed's step has the same work.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np

from . import counts_sdar, seeded_latent, seeded_lm
from .seeded import key_from_seed


def param_shapes(net):
    """The shapes of the program's parameter tree; no value is taken. The
    smallest call the model takes: an acted block and the bootstrap's."""
    spec = net.diffusion
    tokens = jax.ShapeDtypeStruct((2 * spec.block, 1), jnp.int32)
    return jax.eval_shape(
        net.init, jax.random.PRNGKey(0),
        {"tokens": tokens, "reveal_step": tokens},
        jax.ShapeDtypeStruct((spec.steps + 1, 1), jnp.bool_), (),
    )


def make_params(shapes, seed: int, model: dict):
    return seeded_latent.make_params(shapes, seed, model, 0.0)


permute_routers = seeded_latent.permute_routers
balance_held = seeded_latent.balance_held


def draw_done(seed: int, steps: int, batch_size: int, done_rate: float,
              model: dict, tiles) -> np.ndarray:
    """``done`` [steps + 1, B] (module docstring). On the host: a draw is
    counted before it is used."""
    S = model["diffusion"]["steps"]
    for j in itertools.count():
        done = np.zeros((steps + 1, batch_size), bool)
        done[::S] = np.random.default_rng([seed, 2, j]).random(
            (steps // S + 1, batch_size)
        ) < S * done_rate
        done[0] = False
        if tiles is None or counts_sdar.attention_tiles(done, model) == tiles:
            return done
        if j == 20000:
            raise ValueError(
                f"no draw of {j} at rate {done_rate} leaves {tiles} tiles"
            )


def draw_reveal(seed: int, blocks: int, batch_size: int,
                spec: dict) -> np.ndarray:
    """``reveal_step`` [D (blocks + 1), B]: the module docstring's draw,
    by the configuration's ``observation``."""
    D, S, first_step = spec["block"], spec["steps"], spec["first_step_reveals"]
    if S != 2:
        raise ValueError("the seeded sampler reveals a block in two steps")
    rng = np.random.default_rng([seed, 3])
    sizes = sorted(int(k) for k in first_step)
    first = rng.choice(
        sizes, size=(blocks, batch_size),
        p=[first_step[str(k)] for k in sizes],
    )
    # a token is of the first step where its place in a uniform order of
    # its block's four lies under the step's size
    order = np.argsort(rng.random((blocks, batch_size, D)), axis=-1)
    reveal = (np.argsort(order, axis=-1) >= first[..., None]).astype(np.int32)
    reveal = reveal.transpose(0, 2, 1).reshape(blocks * D, batch_size)
    return np.concatenate([reveal, np.full((D, batch_size), S, np.int32)])


def make_learn_batch(seed: int, config: dict, unroll_length: int,
                     batch_size: int, done_rate: float, tiles=None):
    """The learn batch of the module docstring; ``unroll_length`` counts
    steps. Everything large is made in one jitted call."""
    model, spec = config["model"]["kwargs"], config["observation"]
    D, S = spec["block"], spec["steps"]
    A, mask = config["num_actions"], spec["mask_id"]
    if (spec["vocab"], mask, D, S) != (
        A, model["diffusion"]["mask_id"], model["diffusion"]["block"],
        model["diffusion"]["steps"],
    ) or unroll_length % S:
        raise ValueError(
            "the observation's vocabulary, mask and block are the model's, "
            "and the unroll a whole number of blocks"
        )
    N, B = unroll_length // S, batch_size
    done = draw_done(seed, unroll_length, B, done_rate, model, tiles)
    reveal = draw_reveal(seed, N, B, spec)
    acted = D * N
    step = S * (np.arange(acted)[:, None] // D) + reveal[:acted]

    def make(key, done, reveal, step):
        ks = jax.random.split(jax.random.fold_in(key, 2), 4)
        rank = seeded_lm.zipf_tokens(
            ks[0], (D * (N + 1), B), A - 1, spec["zipf_s"]
        )
        tokens = rank + (rank >= mask)  # the ids that are not the mask's
        return {
            "obs": {"tokens": tokens, "reveal_step": reveal},
            "done": done,
            "rewards": jnp.abs(
                jax.random.normal(ks[2], (S * N + 1, B), jnp.float32)
            ) * jnp.linspace(0.1, 1.0, B, dtype=jnp.float32),
            "actions": tokens[:acted],
            "action_step": step,
            "behavior_logits": jax.random.normal(
                ks[3], (acted, B, A), jnp.float32
            ),
            "core_state": (),
        }

    return jax.jit(make)(
        key_from_seed(seed), done, reveal, step.astype(np.int32)
    )
