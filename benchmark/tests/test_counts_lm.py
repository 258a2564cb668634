"""``lib/counts_lm.py`` against numbers worked by hand, and the readers of
the language-model cell on a hand-made summary."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import counts_lm, peaks

from helpers import BENCH, REPO
import test_harness

bench_run = test_harness.bench_run


def model():
    with open(os.path.join(BENCH, "configs", "mellum2_share8.json")) as f:
        return json.load(f)["model"]["kwargs"]


def brute_pairs(seg, window):
    n = 0
    for i in range(len(seg)):
        for j in range(i + 1):
            if seg[i] == seg[j] and (window is None or i - j < window):
                n += 1
    return n


def test_visible_pairs_and_tiles_against_brute_force():
    rng = np.random.default_rng(0)
    done = rng.random(64) < 0.08
    seg = counts_lm.segments(done)
    for window in (None, 5, 16, 100):
        assert counts_lm.visible_pairs(seg, window) == brute_pairs(
            seg, window
        )
    # no boundary, no window: the causal triangle, whole tiles of it
    flat = counts_lm.segments(np.zeros(64, bool))
    assert counts_lm.visible_pairs(flat, None) == 64 * 65 // 2
    assert counts_lm.visible_tiles(flat, 16, None) == 10
    # window 17 reaches one block back, and only that
    assert counts_lm.visible_tiles(flat, 16, 17) == 4 + 3
    assert counts_lm.visible_tiles(flat, 16, 16) == 4 + 3
    assert counts_lm.visible_tiles(flat, 16, 1) == 4
    # a boundary at the start of every block: only the diagonal is left
    cut = np.zeros(64, bool)
    cut[[16, 32, 48]] = True
    assert counts_lm.visible_tiles(counts_lm.segments(cut), 16, None) == 4


def test_the_steps_flops_by_hand():
    """One sequence of 8,192 tokens, no boundary, every held expert at the
    mean load (8 layers x 8,192 assignments)."""
    m = model()
    parts = counts_lm.forward_flops(
        m, 8192, 8 * 8192, np.zeros(8192, bool)
    )
    # q and o are 2304x512, k and v 2304x128: 2,949,120 weights a layer
    assert parts["projections"] == 8 * 8192 * 2 * 2949120
    assert parts["router"] == 8 * 8192 * 2 * 2304 * 64
    assert parts["experts"] == 8 * 8192 * 3 * 2 * 2304 * 896
    assert parts["experts"] == pytest.approx(0.81e12, rel=0.01)
    assert parts["head"] == 8192 * 2 * 2304 * 12289
    # six sliding layers see min(i + 1, 1024) keys, two full ones i + 1
    sliding = 1024 * 1025 // 2 + (8192 - 1024) * 1024
    full = 8192 * 8193 // 2
    assert parts["attention_pairs"] == (6 * sliding + 2 * full) * 4 * 128 * 4
    # tiles of 512: a sliding layer's query block reaches 3 key blocks (2
    # for the second, 1 for the first), a full layer's the whole triangle
    tiles = 6 * (1 + 2 + 14 * 3) + 2 * (16 * 17 // 2)
    assert parts["attention_tiles"] == tiles * 512 * 512 * 4 * 128 * 4
    total = counts_lm.train_flops(parts)
    assert total == 3 * (sum(parts.values()) - parts["attention_tiles"])
    assert 5.0e12 < total < 6.5e12  # the issue counted 5.8 TFLOP
    p = peaks.peaks("TPU v5 lite")
    e = counts_lm.experts_least(m, 8 * 8192, p)
    assert e["bound_by"] == "flops"
    assert e["least_seconds"] == pytest.approx(3 * parts["experts"] / 197e12)
    a = counts_lm.attention_least(m, parts, 8192, p)
    assert a["flops"] == 3 * parts["attention_tiles"]
    # 52.65M a layer, 56.6M of embedding and head: ISSUE.md's 477.8M
    assert counts_lm.parameters(m) == 477_798_913
    whole = counts_lm.step_least(m, parts, 8192, 8 * 8192, p)
    assert whole["flops"] == total and whole["bound_by"] == "flops"
    # 1.9 GB of parameters, 1.2 GB of logits, 2.8 GB of positions' rows,
    # 1.9 GB of assignments' rows
    assert whole["least_bytes"] == (
        4 * 477_798_913 + 3 * 4 * 8192 * 12288
        + 4 * 8192 * (8 * (4 * 2304 + 128 * 10) + 2304)
        + 4 * 65536 * (2 * 2304 + 3 * 896)
    )
    assert 7.5e9 < whole["least_bytes"] < 8.5e9


def test_the_readers_on_a_hand_made_summary(capsys):
    m = model()
    done = np.zeros(8192, bool)
    readings = {
        "summary": {"window_s": 2.0, "busy_s": 1.9, "chips": [
            {"programs": {"jit_step": {"count": 10, "seconds": 1.8},
                          "jit_other": {"count": 1, "seconds": 0.05}}}]},
        # seconds in the window, over its ten steps of 0.18 s
        "scope_seconds": {
            "moolib.moe.experts": 0.4, "moolib.moe.route": 0.1,
            "moolib.moe.gather": 0.05, "moolib.moe.combine": 0.05,
            "moolib.lm.attn_core": 0.3, "moolib.lm.head": 0.1,
            "moolib.loss": 0.06, "moolib.vtrace": 0.2, "(none)": 0.5,
        },
        "counters": {"moe_assignments_held": 8 * 8192.0,
                     "moe_load_max": 1500.0, "moe_load_mean": 1000.0},
        "done_column": done, "frames_per_step_per_chip": 8192,
        "steps_per_s": 5.0, "attention_backend": "flash",
    }
    context = {"config": {"model": {"kwargs": m}},
               "device": {"kind": "TPU v5 lite"}}

    def read(name):
        return bench_run.load_reader(name)(readings, context)

    assert read("moe.device_share") == pytest.approx(100 * 0.6 / 1.8)
    assert read("moe.dispatch_device_share") == pytest.approx(100 * 0.2 / 1.8)
    assert read("lm.head_loss_device_share") == pytest.approx(100 * 0.36 / 1.8)
    assert read("vtrace.device_ms_per_step") == pytest.approx(20.0)
    assert read("moe.load_max_over_mean") == pytest.approx(1.5)
    parts = counts_lm.forward_flops(m, 8192, 8 * 8192.0, done)
    assert read("moe.experts_roofline_share") == pytest.approx(
        100 * (3 * parts["experts"] / 197e12) / 0.04
    )
    assert read("attention.core_roofline_share") == pytest.approx(
        100 * (3 * parts["attention_tiles"] / 197e12) / 0.03
    )
    assert read("lm.mfu") == pytest.approx(
        100 * counts_lm.train_flops(parts) * 5.0 / 197e12
    )
    assert read("lm.step_roofline_share") == pytest.approx(
        100 * (counts_lm.train_flops(parts) / 197e12) / 0.18
    )
    assert "[roofline] experts of one step: 65536 assignments held" in (
        capsys.readouterr().out
    )
    # a program without the scopes or the counters (the parent commit):
    # nothing to read, and nothing raised
    bare = {"summary": readings["summary"], "scope_seconds": {"(none)": 1.8},
            "steps_per_s": 5.0, "frames_per_step_per_chip": 8192}
    for name in ("moe.experts_roofline_share", "attention.core_roofline_share",
                 "moe.device_share", "moe.dispatch_device_share",
                 "lm.head_loss_device_share", "vtrace.device_ms_per_step",
                 "moe.load_max_over_mean", "lm.mfu",
                 "lm.step_roofline_share"):
        assert bench_run.load_reader(name)(bare, context) is None
        assert bench_run.load_reader(name)({}, context) is None
