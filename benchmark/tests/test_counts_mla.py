"""``lib/counts_mla.py`` against numbers worked by hand, and the readers of
the latent-attention cell on hand-made readings."""

import json
import os

import numpy as np
import pytest

from benchmark.lib import counts_mla, peaks, readers_latent, xplane

from helpers import BENCH
import test_harness

bench_run = test_harness.bench_run


def model():
    with open(os.path.join(BENCH, "configs", "glm47_flash_share8.json")) as f:
        return json.load(f)["model"]["kwargs"]


def test_the_blocks_a_step_runs():
    assert counts_mla.blocks(model()) == (
        [("latent", "dense")] + [("latent", "sparse")] * 4
        + [("latent", "sparse")]
    )


def test_the_steps_flops_by_hand():
    """One sequence of 8,192 tokens, no boundary, every expert layer at
    the mean load (five of them, the module's one: 4,096 assignments
    each)."""
    m = model()
    T = 8192
    parts = counts_mla.forward_flops(m, T, 5 * 4096, np.zeros(T, bool))
    # a position through one block's attention projections: every weight
    # of the attention once, 21,757,952 of its 21,759,232 parameters (the
    # rest are the two norms' scales)
    weights = (2048 * 768 + 768 * 20 * 256 + 2048 * 576
               + 512 * 20 * 448 + 5120 * 2048)
    assert weights == 21_757_952
    assert parts["mla_projections"] == 6 * T * 2 * weights
    pairs = T * (T + 1) // 2
    assert parts["attention_pairs"] == 6 * pairs * 2 * 20 * (256 + 256)
    assert parts["attention_tiles"] == 6 * 136 * 512 * 512 * 2 * 20 * 512
    assert parts["mlp_dense"] == T * 3 * 2 * 2048 * 10240
    assert parts["router"] == 5 * T * 2 * 2048 * 64
    assert parts["experts_shared"] == 5 * T * 3 * 2 * 2048 * 1536
    assert parts["experts_routed"] == 5 * 4096 * 3 * 2 * 2048 * 1536
    assert parts["mtp_projection"] == T * 2 * 4096 * 2048
    assert parts["heads"] == T * 2 * 2048 * (2 * 19360 + 1)
    forward = sum(v for k, v in parts.items() if k != "attention_tiles")
    assert counts_mla.train_flops(parts) == 3 * forward
    # ISSUE 31's estimate: 5.8 TFLOP forward outside the attention core,
    # 4.1 in it with no boundary
    outside = forward - parts["attention_pairs"]
    assert outside == pytest.approx(5.8e12, rel=0.03)
    assert parts["attention_pairs"] == pytest.approx(4.1e12, rel=0.02)


def test_boundaries_cut_the_attention_and_nothing_else():
    m = model()
    T = 8192
    done = np.zeros(T, bool)
    done[4096] = True
    whole = counts_mla.forward_flops(m, T, 0, np.zeros(T, bool))
    cut = counts_mla.forward_flops(m, T, 0, done)
    half = 4096 * 4097 // 2
    assert cut["attention_pairs"] == 6 * 2 * half * 2 * 20 * 512
    # eight blocks of 512 a half: 36 tiles a half, 72 of 136
    assert cut["attention_tiles"] * 136 == whole["attention_tiles"] * 72
    for name in whole:
        if not name.startswith("attention"):
            assert cut[name] == whole[name]


def test_attention_least_is_bound_by_flops_at_this_shape():
    m = model()
    T = 8192
    parts = counts_mla.forward_flops(m, T, 0, np.zeros(T, bool))
    r = counts_mla.attention_least(m, parts, T, peaks.peaks("TPU v5 lite"))
    assert r["flops"] == 3 * parts["attention_tiles"]
    # q, k, v, o: 20 heads of 256 each, 2 B, three passes, six blocks
    assert r["least_bytes"] == 6 * T * 20 * 4 * 256 * 2 * 3
    assert r["bound_by"] == "flops"
    assert r["least_seconds"] == pytest.approx(r["flops"] / 197e12)


def test_seconds_under_a_scope_counts_the_scopes_inside_it():
    us = 1000.0  # ns
    rows = [
        # a while holding two operations of the module's block
        ("while", "jit(step)/moolib.lm.mtp/while", 0, 10 * us),
        ("fusion.1", "jit(step)/moolib.lm.mtp/block/moolib.moe.experts/dot",
         1 * us, 4 * us),
        ("fusion.2", "jit(step)/moolib.lm.mtp/eh_proj/dot", 5 * us, 7 * us),
        # the stack's experts: not the module's
        ("fusion.3", "jit(step)/block_1/moe/moolib.moe.experts/dot",
         12 * us, 20 * us),
        ("fusion.4", None, 20 * us, 21 * us),
    ]
    planes = {"/device:TPU:0": rows}
    assert readers_latent.seconds_under(
        planes, None, "moolib.lm.mtp") == pytest.approx(10e-6)
    assert readers_latent.seconds_under(
        planes, (2 * us, 6 * us), "moolib.lm.mtp") == pytest.approx(4e-6)
    assert readers_latent.seconds_under(
        planes, None, "moolib.moe.experts") == pytest.approx(11e-6)
    # transforms wrap the name: found by pattern, as lib/scopes.py finds it
    rows.append(("fusion.5", "jit(step)/transpose(jvp(moolib.lm.mtp))/x",
                 30 * us, 31 * us))
    assert readers_latent.seconds_under(
        planes, None, "moolib.lm.mtp") == pytest.approx(11e-6)


def readings(scope_seconds, under):
    summary = {"window_s": 3.0, "busy_s": 2.9, "chips": [
        {"programs": {"jit_step": {"count": 4, "seconds": 2.8}}}]}
    return {
        "summary": summary, "scope_seconds": scope_seconds,
        "seconds_under_mtp": under, "steps_per_s": 1.4,
        "frames_per_step_per_chip": 8192,
        "counters": {"moe_assignments_held": 5 * 4096.0},
        "done_column": np.zeros(8192, bool), "attention_backend": "flash",
    }


def context():
    with open(os.path.join(BENCH, "configs", "glm47_flash_share8.json")) as f:
        config = json.load(f)
    return {"config": config, "device": {"kind": "TPU v5 lite"}}


def test_the_readers_on_hand_made_readings(capsys):
    r = readings({"moolib.lm.attn_core": 0.56, "moolib.lm.mla_proj": 0.28,
                  "moolib.lm.mlp_dense": 0.07, "moolib.moe.shared": 0.14},
                 0.42)
    read = bench_run.load_reader
    assert read("mla.proj_device_share")(r, context()) == pytest.approx(10.0)
    assert read("mlp.dense_shared_device_share")(
        r, context()) == pytest.approx(7.5)
    assert read("mtp.device_share")(r, context()) == pytest.approx(15.0)
    # attention: 0.56 s in four steps of the window = 140 ms a step
    parts = counts_mla.forward_flops(
        model(), 8192, 5 * 4096.0, np.zeros(8192, bool))
    least = 3 * parts["attention_tiles"] / 197e12
    assert read("mla.core_roofline_share")(
        r, context()) == pytest.approx(100 * least / 0.140)
    assert "[roofline] latent attention cores of one step" in (
        capsys.readouterr().out)
    mfu = read("lm_latent.mfu")(r, context())
    assert mfu == pytest.approx(
        100 * counts_mla.train_flops(parts) * 1.4 / 197e12)
    assert 0 < mfu < 100
    assert "[flops] forward, by part: " in capsys.readouterr().out


def test_the_readers_say_nothing_where_there_is_nothing_to_read():
    """A program without the scopes or counters (the parent of the PR that
    added them), or a run without a trace: None, not an error."""
    bare = {"summary": None, "scope_seconds": None}
    other = readings({"moolib.lm.attn_proj": 0.5}, None)
    for name in ("mla.core_roofline_share", "mla.proj_device_share",
                 "mlp.dense_shared_device_share", "mtp.device_share",
                 "lm_latent.mfu"):
        assert bench_run.load_reader(name)(bare, context()) is None
    for name in ("mla.proj_device_share", "mlp.dense_shared_device_share",
                 "mtp.device_share"):
        assert bench_run.load_reader(name)(other, context()) is None
    # the other decoder's description has no latent kind: no count of it
    with open(os.path.join(BENCH, "configs", "mellum2_share8.json")) as f:
        mellum = {"config": json.load(f), "device": {"kind": "TPU v5 lite"}}
    assert bench_run.load_reader("lm_latent.mfu")(other, mellum) is None
    assert bench_run.load_reader("mla.core_roofline_share")(
        dict(other, scope_seconds={"moolib.lm.attn_core": 0.1}), mellum
    ) is None
