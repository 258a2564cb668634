"""The FLOP and least-byte counts against hand counts and against the
program's own parameter tree."""

import json
import os

import jax
import pytest

from benchmark.lib import counts, peaks, program

from helpers import BENCH


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_one_convolution_by_hand():
    # 3x3, 4 -> 16 channels at 84x84, stride 1, SAME: 84*84 outputs, each
    # 3*3*4 multiply-accumulates per output channel.
    assert counts.conv2d_flops(84, 84, 3, 3, 4, 16) == 2 * 84 * 84 * 36 * 16
    records, input_bytes = counts.walk([
        {"op": "input", "shape": [84, 84, 4], "bytes": 1},
        {"op": "conv", "out": 16, "kernel": 3, "stride": 1},
    ])
    (name, flops, params, elems, nbytes), = records
    assert flops == 8_128_512
    assert params == 3 * 3 * 4 * 16 + 16
    assert (elems, nbytes) == (84 * 84 * 16, 84 * 84 * 16 * 2)
    assert input_bytes == 84 * 84 * 4


def test_one_lstm_step_by_hand():
    # four gates, each an input product 256x256 and a recurrent one 256x256
    assert counts.lstm_flops(256, 256) == 4 * (2 * 256 * 256 + 2 * 256 * 256)


def test_strided_convolutions_round_up():
    records, _ = counts.walk(config("nethack_lstm")["architecture"])
    sizes = [r[0].split("@")[1] for r in records if r[0].startswith("conv")]
    assert sizes == ["11x40", "6x20", "3x10"]


@pytest.mark.parametrize("name", ["impala_deep_atari", "nethack_lstm"])
def test_parameter_count_is_the_programs(name):
    cfg = config(name)
    shapes = program.param_shapes(program.build_model(cfg), cfg)
    n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert counts.train_step(cfg, 1)["params"] == n


def test_atari_step_matches_the_programs_own_count():
    from moolib_tpu.utils import flops

    cfg = config("impala_deep_atari")
    frames = 21 * 256
    assert counts.train_step(cfg, frames)["flops"] == flops.impala_train_flops(
        frames
    )


def test_roofline_names_its_bound_and_unknown_chip_is_an_error():
    cfg = config("impala_deep_atari")
    r = counts.roofline(cfg, 21 * 256, peaks.peaks("TPU v5 lite"))
    assert r["bound_by"] in ("flops", "bytes")
    assert r["least_seconds"] == max(r["flops_seconds"], r["bytes_seconds"])
    with pytest.raises(ValueError, match="no peaks recorded"):
        peaks.peaks("cpu")
