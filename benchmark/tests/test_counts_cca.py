"""``lib/counts_cca.py``: the boundary count against the program's own
(``models/lm.py:cca_taps_cut``), the mixing as written, the parameters
against the program's shapes, and the parts of ``zaya1_share8``'s step
against the issue's arithmetic."""

import json
import os

import numpy as np
import pytest

from helpers import REPO

from benchmark.lib import counts_cca, peaks


def model():
    with open(os.path.join(
            REPO, "benchmark", "configs", "zaya1_share8.json")) as f:
        return json.load(f)["model"]["kwargs"]


@pytest.mark.parametrize("T,done_at", [
    (8192, ()), (8192, (0,)), (8192, (1, 2)), (8192, (700, 701, 4000, 8191)),
    (200, (64, 191)), (40, (13, 27)), (5, (0, 4)),
])
def test_the_taps_cut_are_the_programs(T, done_at):
    import jax.numpy as jnp

    from moolib_tpu.models.lm import Cca, cca_taps_cut

    done = np.zeros(T, bool)
    done[list(done_at)] = True
    got = counts_cca.taps_cut(model(), done)
    seg = jnp.asarray(np.cumsum(done)[None], jnp.int32)
    assert got == 5 * int(cca_taps_cut(seg, Cca(2, 2)))
    # two convolutions of one tap back and the value shift, five blocks:
    # the call's first position and every boundary after it
    assert got == 5 * 3 * (1 + sum(1 for t in done_at if t > 0))


def test_longer_convolutions_lose_more_taps_at_a_boundary():
    m = model()
    kinds = {"cca": dict(m["attention_kinds"]["cca"],
                         cca={"time0": 4, "time1": 2})}
    one = dict(m, attention_kinds=kinds,
               layers=[{"attention": "cca", "mlp": "sparse"}])
    done = np.zeros(64, bool)
    done[[10, 12]] = True
    # taps 1, 2, 3 back of conv0: 3 + 3 + 2 of them cut (the boundary at
    # 12 takes position 12's three and 13's two, 14's one; at 10 the same
    # less what 12 took: positions 10, 11 x taps), counted by hand below
    by_hand = 0
    seg = np.cumsum(done)
    for back in (1, 2, 3):
        by_hand += sum(
            1 for t in range(64) if t < back or seg[t - back] != seg[t])
    by_hand += 2 * sum(1 for t in range(64) if t < 1 or seg[t - 1] != seg[t])
    assert counts_cca.taps_cut(one, done) == by_hand


def test_the_mixing_is_counted_as_written():
    m = model()
    parts = counts_cca.forward_flops(m, 8192, 19275.0, np.zeros(8192, bool))
    # a token and layer: two taps of ten 128 x 128 matrices
    assert parts["cca_conv"] == 5 * 8192 * 2 * 2 * 10 * 128 * 128
    least = counts_cca.mix_least(m, parts, 8192, peaks.peaks("TPU v5 lite"))
    assert least["flops"] == 3 * parts["cca_conv"]
    # [qt | kt] read and [qh | kh] written, 1,280 wide, 2 B, 3x
    assert least["least_bytes"] == 3 * 5 * 8192 * 2 * 1280 * 2
    assert least["bound_by"] == "bytes"


def test_the_parts_are_the_issues_arithmetic():
    m = model()
    T = 8192
    done = np.zeros(T, bool)
    parts = counts_cca.forward_flops(m, T, 5 * 3855.0, done)
    per_token_layer = {
        k: parts[k] / (5 * T) for k in ("cca_projections", "cca_conv",
                                        "router", "attention_pairs")
    }
    assert per_token_layer["cca_projections"] == 2 * 2048 * (
        1024 + 256 + 256 + 1024)  # 10.5 MFLOP
    assert abs(per_token_layer["cca_conv"] - 0.655e6) < 1e3
    assert abs(per_token_layer["router"] - 1.32e6) < 1e4
    # one episode of 8,192: a token sees 4,096.5 keys on average
    assert abs(per_token_layer["attention_pairs"] - 16.8e6) < 0.1e6
    assert parts["experts"] == 5 * 3855 * 3 * 2 * 2048 * 2048
    assert parts["head"] == T * 2 * 2048 * 32785
    # about 8 model TFLOP a step: the issue's 8.3 at one episode
    assert 8.0e12 < counts_cca.train_flops(parts) < 8.6e12
    # boundaries take from the attention and nothing else
    done[[2000, 4100, 6000]] = True
    cut = counts_cca.forward_flops(m, T, 5 * 3855.0, done)
    assert cut["attention_pairs"] < 0.4 * parts["attention_pairs"]
    assert cut["attention_tiles"] >= cut["attention_pairs"]
    assert {k: v for k, v in cut.items() if "attention" not in k} == {
        k: v for k, v in parts.items() if "attention" not in k}
    core = counts_cca.core_least(m, cut, T, peaks.peaks("TPU v5 lite"))
    assert core["flops"] == 3 * cut["attention_tiles"]


def test_the_parameters_are_the_programs():
    import jax

    from benchmark.lib import program, seeded_cca

    with open(os.path.join(
            REPO, "benchmark", "configs", "zaya1_share8.json")) as f:
        config = json.load(f)
    shapes = seeded_cca.param_shapes(program.build_model(config))
    assert counts_cca.parameters(config["model"]["kwargs"]) == sum(
        x.size for x in jax.tree_util.tree_leaves(shapes)) == 601_748_064
