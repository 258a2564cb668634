"""``lib/counts_mhc.py`` against numbers worked by hand, and the two
readers of the residual mixing on hand-made readings."""

import json
import os

import pytest

from benchmark.lib import counts_mhc, peaks

from helpers import BENCH
import test_harness

bench_run = test_harness.bench_run


def model():
    with open(os.path.join(BENCH, "configs", "xing4_share8.json")) as f:
        return json.load(f)["model"]["kwargs"]


def test_the_mixing_of_one_step_by_hand():
    """Five blocks, two mixed sublayers each, 4,096 tokens, four streams
    of 3,584."""
    m = model()
    assert counts_mhc.sublayers(m) == 10
    one = counts_mhc.per_sublayer_token(m)
    # forward: 4 streams read twice and written once, h and y: 14 rows of
    # 3,584 at 2 B; backward 15 rows
    assert one["forward_bytes"] == 14 * 3584 * 2 == 100_352
    assert one["backward_bytes"] == 15 * 3584 * 2 == 107_520
    # the phi product 14,336 x 24, the remix 16 x 3,584, two sums of 4
    assert one["forward_flops"] == 2 * (14336 * 24 + 16 * 3584 + 8 * 3584)
    assert one["forward_flops"] == 860_160
    v5e = peaks.peaks("TPU v5 lite")
    r = counts_mhc.mixing_least(m, 4096, v5e)
    assert r["least_bytes"] == 10 * 4096 * 207_872 == 8_514_437_120
    assert r["flops"] == 3 * 10 * 4096 * 860_160
    assert r["bound_by"] == "bytes"
    assert r["least_seconds"] == pytest.approx(8_514_437_120 / 819e9)
    assert r["least_seconds"] == pytest.approx(10.4e-3, rel=0.01)
    # 0.9% of the step's model FLOPs as lib/counts_mla.py counts them
    # (11.6 TFLOP), which leaves them out
    assert r["flops"] == pytest.approx(1.057e11, rel=0.01)


def readings(scope_seconds):
    """One chip, a window that holds two steps of 0.25 s of the step
    program, as ``lib/readers.py`` reads them."""
    return {
        "scope_seconds": scope_seconds,
        "frames_per_step_per_chip": 4096,
        "program": "jit_step",
        "summary": {"chips": [
            {"programs": {"jit_step": {"count": 2, "seconds": 0.5}}}
        ]},
    }


def test_the_two_readers_on_hand_made_readings():
    context = {"config": {"model": {"kwargs": model()}},
               "device": {"kind": "TPU v5 lite"}}
    share = bench_run.load_reader("mhc.device_share")
    roofline = bench_run.load_reader("mhc.stream_roofline_share")
    r = readings({"moolib.lm.hc_mix": 0.02, "moolib.lm.hc_pre": 0.01,
                  "moolib.lm.hc_post": 0.07, "moolib.lm.attn_core": 0.1})
    # a step is 0.25 s of device time and 0.05 s of it the mixing's
    assert share(r, context) == pytest.approx(100 * 0.1 / 0.5)
    # 0.05 s of mixing a step: 10.4 ms at the roofline is 20.8% of it
    assert roofline(r, context) == pytest.approx(
        100 * (8_514_437_120 / 819e9) / 0.05)
    # a program without the scopes (the parent), or a description without
    # the skeleton: nothing to read, and nothing raised
    bare = readings({"moolib.lm.attn_core": 0.1})
    assert share(bare, context) is None and roofline(bare, context) is None
    assert share({}, context) is None and roofline({}, context) is None
    plain = {"config": {"model": {"kwargs": dict(model(), residual=None)}},
             "device": {"kind": "TPU v5 lite"}}
    assert roofline(r, plain) is None
