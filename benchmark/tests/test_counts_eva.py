"""``lib/counts_eva.py`` against a brute-force mask at small sizes, with
and without boundaries, against numbers worked by hand at the cell's
size, and the three readers of the chunk-summary cell on hand-made
readings."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import counts_eva, peaks

from helpers import BENCH
import test_harness

bench_run = test_harness.bench_run


def model(**over):
    with open(os.path.join(BENCH, "configs", "evabyte_pp8.json")) as f:
        return dict(json.load(f)["model"]["kwargs"], **over)


def small(window=8, chunk=2, block=4, repeat=2):
    return {
        "layers": [{"attention": "eva", "mlp": "dense", "repeat": repeat}],
        "attention_kinds": {"eva": {"window": window, "rope": {},
                                    "eva": {"chunk_size": chunk}}},
        "attention_block": block, "hidden_size": 32, "head_dim": 8,
        "num_heads": 4, "num_kv_heads": 4, "intermediate_size": 48,
        "vocab_size": 20, "num_pred_heads": 8,
    }


def brute(done, window, chunk, block):
    """Both masks written out, and the tiles that hold a True."""
    seg = np.cumsum(done)
    T = len(seg)
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    local = (s <= t) & (s // window == t // window) & (seg[s] == seg[t])
    first = np.arange(0, T, chunk)
    last = np.minimum(first + chunk - 1, T - 1)
    earlier = (first[None, :] // window < t // window) & (
        seg[last][None, :] == seg[t])
    bk = min(block, len(first))

    def tiles(mask, bq, bk):
        return sum(
            bool(mask[i:i + bq, j:j + bk].any())
            for i in range(0, mask.shape[0], bq)
            for j in range(0, mask.shape[1], bk))

    return {
        "local_pairs": int(local.sum()), "summary_pairs": int(earlier.sum()),
        "local_tiles": tiles(local, block, block),
        "summary_tiles": tiles(earlier, block, bk),
        "chunks_cut": int(((seg[first] != seg[last])
                           & (first + chunk <= T)).sum()),
    }


@pytest.mark.parametrize("steps,done_at", [
    (40, ()), (40, (13,)), (40, (16,)), (40, (5, 6, 21)), (40, (8, 24, 25)),
    (64, (1, 33, 34, 35, 63)), (16, (7,)), (8, ()),
])
def test_pairs_and_tiles_against_a_written_out_mask(steps, done_at):
    done = np.zeros(steps, bool)
    done[list(done_at)] = True
    m = small()
    assert counts_eva.attention_counts(m, done) == brute(done, 8, 2, 4)
    assert counts_eva.visible_tiles(done[:, None], m) == 2 * sum(
        brute(done, 8, 2, 4)[k] for k in ("local_tiles", "summary_tiles"))


def test_random_boundaries_against_a_written_out_mask():
    rng = np.random.default_rng(0)
    m = small(window=16, chunk=4, block=8)
    for _ in range(40):
        done = rng.random(128) < 0.05
        assert counts_eva.attention_counts(m, done) == brute(done, 16, 4, 8)


def test_the_cells_step_by_hand():
    """16,384 bytes in one episode: 8 windows of 2,048, 1,024 chunks."""
    m = model()
    done = np.zeros(16384, bool)
    counts = counts_eva.attention_counts(m, done)
    # a window's causal triangle; a query of window w reads 128 w chunks
    assert counts["local_pairs"] == 8 * 2048 * 2049 // 2
    assert counts["summary_pairs"] == 2048 * 128 * sum(range(8))
    # 4 blocks of 512 a window: 10 tiles; chunk blocks of 512 hold four
    # windows: windows 1-7 read the first, windows 5-7 the second
    assert counts["local_tiles"] == 8 * 10
    assert counts["summary_tiles"] == 4 * 7 + 4 * 3
    assert counts["chunks_cut"] == 0
    assert counts_eva.visible_tiles(done[:, None], m) == 480
    parts = counts_eva.forward_flops(m, 16384, done)
    assert parts["projections"] == 4 * 16384 * 2 * 4096 * 4096 * 4
    assert parts["mlp_dense"] == 4 * 16384 * 6 * 4096 * 11008
    assert parts["heads"] == 16384 * 2 * 4096 * (8 * 320 + 1)
    per_pair = 2 * 2 * 128 * 32
    assert parts["attention_pairs"] == 4 * per_pair * (
        counts["local_pairs"] + counts["summary_pairs"])
    assert parts["attention_tiles"] == 4 * per_pair * 120 * 512 * 512
    flops = counts_eva.train_flops(parts)
    assert flops == pytest.approx(85.4e12, rel=0.01)
    dense = 3 * (parts["projections"] + parts["mlp_dense"])
    assert dense / flops == pytest.approx(0.93, abs=0.01)
    v5e = peaks.peaks("TPU v5 lite")
    r = counts_eva.attention_least(m, parts, 16384, v5e)
    assert r["flops"] == 3 * parts["attention_tiles"]
    assert r["bound_by"] == "flops"
    assert r["least_bytes"] == 4 * 128 * (16384 * 128 + 1024 * 64) * 6


def test_a_boundary_hides_summary_tiles_and_local_ones():
    m = model()
    done = np.zeros(16384, bool)
    done[5 * 2048 + 700] = True  # in window 5's second block of 512
    counts = counts_eva.attention_counts(m, done)
    # the later blocks of window 5 lose the first block's keys
    assert counts["local_tiles"] == 80 - 2
    # the second episode's query blocks (window 5's last two, all of
    # windows 6 and 7) read nothing of the first four windows any more,
    # and window 5's last two nothing of window 4 either
    assert counts["summary_tiles"] == 40 - 2 - 8 - 2
    assert counts["chunks_cut"] == 1


def readings(scope_seconds):
    """One chip, a window that holds four steps of 1 s of the step
    program, as ``lib/readers.py`` reads them."""
    step = {"count": 4, "seconds": 4.0}
    return {
        "scope_seconds": scope_seconds,
        "summary": {"chips": [{"programs": {"jit_step": step}}]},
        "program": "jit_step",
        "frames_per_step_per_chip": 16384,
        "done_column": np.zeros(16384, bool),
        "steps_per_s": 1.0,
        "attention_backend": "flash",
        "counters": {},
    }


def context():
    with open(os.path.join(BENCH, "configs", "evabyte_pp8.json")) as f:
        config = json.load(f)
    return {"config": config, "cell": {}, "chips": 1,
            "device": {"kind": "TPU v5 lite"}}


def test_the_three_readers_on_hand_made_readings(capsys):
    r = readings({"moolib.lm.attn_core": 0.4, "moolib.lm.eva_summary": 0.06,
                  "moolib.lm.eva_merge": 0.02, "moolib.lm.head": 1.0})
    ctx = context()
    share = bench_run.load_reader("eva.summary_device_share")(r, ctx)
    assert share == pytest.approx(100 * 0.08 / 4.0)
    roofline = bench_run.load_reader("eva.core_roofline_share")(r, ctx)
    least = 3 * 4 * 16384 * 120 * 512 * 512 / 197e12
    assert roofline == pytest.approx(100 * least / 0.1)
    mfu = bench_run.load_reader("lm_eva.mfu")(r, ctx)
    assert mfu == pytest.approx(100 * 85.4e12 / 197e12, rel=0.01)
    out = capsys.readouterr().out
    assert "[roofline] chunk-summary attention cores" in out
    assert "[flops] forward, by part" in out


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    ctx = context()
    for name in ("eva.summary_device_share", "eva.core_roofline_share"):
        read = bench_run.load_reader(name)
        assert read(readings({"moolib.lm.head": 1.0}), ctx) is None
        assert read({"counters": {}}, ctx) is None
    # a description without the kind: the parent's program, another model
    with open(os.path.join(BENCH, "configs", "glm47_flash_share8.json")) as f:
        other = dict(ctx, config=json.load(f))
    r = readings({"moolib.lm.attn_core": 0.4})
    assert bench_run.load_reader("lm_eva.mfu")(r, other) is None
    assert bench_run.load_reader("eva.core_roofline_share")(r, other) is None
