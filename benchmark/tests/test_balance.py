"""Every seed the same work (``lib/seeded_lm.py``). The labelling of the
experts held (``balance_held``): the subset search on numbers worked by
hand, the permutation, and on the rehearsal's tiny model that every layer
ends at its mean load with the layers before it settled first. The episode
boundaries (``draw_done``): every seed's draw leaves the attention the
tiles the cell states."""

import json
import os

import jax
import numpy as np
import pytest

from helpers import TESTS
from benchmark.lib import program, seeded_lm


def test_nearest_subset_finds_the_sum_on_numbers_worked_by_hand():
    loads = np.array([100, 1, 2, 3, 50, 44, 7, 900])
    rng = np.random.default_rng(0)
    # 1107 / 8 * 2 = 276.75: no pair comes nearer than 100 + 50... but
    # 900 is out of reach, so the nearest pair is 100 + 50 = 150
    assert list(seeded_lm.nearest_subset(loads, 2, 276.75, rng)) == [0, 4]
    # three of them: 100 + 50 + 44 = 194 is the nearest to 200 under 900
    assert list(seeded_lm.nearest_subset(loads, 3, 200.0, rng)) == [0, 4, 5]


@pytest.mark.parametrize("held", [(0, 2), (3, 2), (6, 2)])
def test_held_first_is_a_permutation_that_seats_the_chosen(held):
    loads = np.array([100, 1, 2, 3, 50, 44, 7, 900])
    perm = seeded_lm.held_first(loads, held, np.random.default_rng(1))
    first, count = held
    assert sorted(perm) == list(range(8))
    assert sorted(perm[first:first + count]) == [0, 4]  # 150, of a mean 277
    rest = np.delete(perm, range(first, first + count))
    assert list(rest) == sorted(rest)  # the others keep their order


def test_every_layer_of_the_tiny_model_ends_at_its_mean_load():
    with open(os.path.join(TESTS, "rehearsal_lm", "benchmark", "configs",
                           "tiny_lm.json")) as f:
        config = json.load(f)
    net = program.build_model(config)
    loads_fn = jax.jit(program.resolve(config["router_loads_factory"])(net))
    held = tuple(config["model"]["kwargs"]["experts_held"])
    first, count = held
    seed = 2147483999
    batch = seeded_lm.make_learn_batch(seed, config, 63, 2, 0.05)
    seeded = seeded_lm.make_params(seeded_lm.param_shapes(net), seed)
    params, perms, before, after = seeded_lm.balance_held(
        seeded, loads_fn, batch, held, seed
    )
    loads = np.asarray(loads_fn(params, batch["obs"], batch["done"]))
    E = loads.shape[1]
    assert list(loads[:, first:first + count].sum(axis=1)) == after
    assert before != after
    as_seeded = np.asarray(loads_fn(seeded, batch["obs"], batch["done"]))
    assert before[0] == as_seeded[0, first:first + count].sum()
    for layer, got in zip(loads, after):
        mean = layer.sum() * count / E
        # no single swap comes nearer, and few tokens leave coarse sums
        assert abs(got - mean) <= 0.05 * mean
    # the same labelling again from the permutations alone
    again = seeded_lm.permute_routers(seeded, perms)
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(a, b)
    # only the routers' columns moved
    moved = [
        jax.tree_util.keystr(path)
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(seeded)[0],
            jax.tree_util.tree_leaves(params))
        if not np.array_equal(a, b)
    ]
    assert moved and all(m.endswith("router']") for m in moved)


def tiny_config():
    with open(os.path.join(TESTS, "rehearsal_lm", "benchmark", "configs",
                           "tiny_lm.json")) as f:
        return json.load(f)


def test_attention_tiles_by_hand():
    """T+1 = 32 in tiles of 16: the sliding layer (window 8) and the full
    one each see the two diagonal tiles and the one under them, 6 in all;
    a boundary at frame 16 parts the two halves and takes that one away
    from both."""
    model = tiny_config()["model"]["kwargs"]
    done = np.zeros((32, 1), bool)
    assert seeded_lm.attention_tiles(done, model) == 6
    done[16] = True
    assert seeded_lm.attention_tiles(done, model) == 4
    assert seeded_lm.attention_tiles(np.tile(done, (1, 3)), model) == 12


@pytest.mark.parametrize("seed", [3, 2147483999, 2**31 + 5])
def test_every_seeds_boundaries_leave_the_tiles_the_cell_states(seed):
    model = tiny_config()["model"]["kwargs"]
    for tiles in (4, 6):
        done = seeded_lm.draw_done(seed, (32, 1), 0.05, model, tiles)
        assert seeded_lm.attention_tiles(done, model) == tiles
    free = seeded_lm.draw_done(seed, (32, 1), 0.05, None, None)
    again = seeded_lm.draw_done(seed, (32, 1), 0.05, model, None)
    np.testing.assert_array_equal(free, again)  # the seed's first draw
    batch = seeded_lm.make_learn_batch(seed, tiny_config(), 31, 1, 0.05,
                                       tiles=4)
    assert seeded_lm.attention_tiles(np.asarray(batch["done"]), model) == 4


def test_a_count_no_draw_reaches_is_an_error():
    with pytest.raises(ValueError, match="leaves 5 tiles"):
        seeded_lm.draw_done(1, (32, 1), 0.05,
                            tiny_config()["model"]["kwargs"], 5)
