"""The cell of the decoder under block diffusion whose action is a
denoising step: its driver end to end on the CPU at a tiny size, through
``run.py`` under a manifest of its own (``rehearsal_sdar/``), ``correct``
false where it should be, ``lib/counts_sdar.py`` against the definitions,
and what ``BENCHMARK.json`` says of the cell."""

import collections
import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_sdar", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_sdar_run.py")
CELL, CONFIG = "sdar_learner_8k", "sdar_share8"
NEW_METRICS = ["blockdiff.core_roofline_share", "blockdiff.local_device_share",
               "blockdiff.rows_device_share", "lm_blockdiff.mfu"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "moe.device_share",
               "moe.dispatch_device_share", "moe.load_max_over_mean",
               "lm.head_loss_device_share", "vtrace.device_ms_per_step",
               "moe.experts_roofline_share"]


def load(kind, name, root=os.path.join(TESTS, "rehearsal_sdar", "benchmark")):
    with open(os.path.join(root, kind, name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_its_manifest_keeps_to_the_contract():
    test_harness.test_manifest_keeps_to_the_contract(MANIFEST)


def test_every_new_metric_has_an_entry_and_a_reader():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    names = list(per_layer)
    at = [names.index(name) for name in NEW_METRICS]
    assert at == list(range(at[0], at[0] + len(at)))  # by name, not by place
    for name in NEW_METRICS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "learner_env_steps_per_s"
        assert entry["unit"] == "%"
        assert callable(test_harness.bench_run.load_reader(name))
    assert per_layer["blockdiff.core_roofline_share"]["layer"] == "kernels"
    assert per_layer["lm_blockdiff.mfu"]["source"] == "host_clock"
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    for name in names:  # other mechanisms', or counted for other models
        if name not in NEW_METRICS + APPENDED_TO:
            assert CELL not in per_layer[name].get("workloads", []), name


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["name"], entry["config"], entry["chips"],
            entry["traffic"]) == (
        CELL, CONFIG, 1, "resident_b1_n4094_blk4_s2_done2k")
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    config = load("configs", CONFIG, os.path.join(REPO, "benchmark"))
    assert config_entry["reduced"] == config["reduced"]
    assert config_entry["source"] == config["source"]
    assert config["reference"] == CONFIG
    cell = load("workloads", CELL, os.path.join(REPO, "benchmark"))
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"], cell["attention_backend"],
            cell["driver"]) == (
        4094, 1, 1 / 2048, 2, 3, 3, 3, "flash", "lm_blockdiff_learner_step")
    assert set(cell["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_leaf_gap",
        "change_leaf_gap"}
    assert cell["limits_why"] and len(cell["why"]) > 200


def test_the_cells_tile_count_is_the_commonest_that_hides_a_tile():
    """Of the draws at the cell's rate the commonest count is the whole
    triangle's, 136 tiles a layer (no boundary, or one that hides no
    tile): passed over, the episode rule would have nothing to do on most
    seeds. Of the counts below it 82 and 80 lead (a boundary in one of
    the four middle rows of tiles): the cell fixes 82 a layer."""
    from benchmark.lib import counts_sdar, seeded_sdar

    cell = load("workloads", CELL, os.path.join(REPO, "benchmark"))
    model = load("configs", CONFIG, os.path.join(REPO, "benchmark"))[
        "model"]["kwargs"]
    steps = cell["unroll_length"]
    seen = collections.Counter()
    for s in range(1500):
        done = np.zeros((steps + 1, 1), bool)
        done[::2] = np.random.default_rng([s, 2, 0]).random(
            (steps // 2 + 1, 1)) < 2 * cell["done_rate"]
        done[0] = False
        seen[counts_sdar.visible_tiles(model, done[:, 0])] += 1
    assert cell["attention_tiles"] == 6 * 82
    top = [count for count, _ in seen.most_common(3)]
    assert top[0] == 136 and 82 in top
    for seed in (3, 2 ** 31 + 77):
        done = seeded_sdar.draw_done(
            seed, steps, 1, cell["done_rate"], model, cell["attention_tiles"])
        assert not done[0].any() and done.any() and not done[1::2].any()
        assert counts_sdar.attention_tiles(done, model) == 492


@pytest.mark.parametrize("S,boundaries", [(2, (3, 5)), (4, ())])
def test_the_counts_against_the_visibility_rule_as_a_matrix(S, boundaries):
    """``counts_sdar``'s pairs and tiles against the rule written out pair
    by pair, at a size where the matrix fits."""
    from benchmark.lib import counts_sdar

    D, N, block = 4, 15, 16
    model = {"diffusion": {"block": D, "steps": S, "mask_id": 0},
             "layers": [{"attention": "a", "mlp": "sparse", "repeat": 3}],
             "attention_block": block}
    done = np.zeros((S * N + 1, 1), bool)
    for b in boundaries:
        done[S * b] = True
    L, C = D * (N + 1), 1 + S
    episode = np.cumsum(done[::S, 0])[np.arange(L) // D]
    blk = np.arange(L) // D
    earlier = (episode[:, None] == episode[None, :]) & (
        blk[None, :] < blk[:, None])
    own = blk[:, None] == blk[None, :]
    assert counts_sdar.pairs(model, done[:, 0]) == C * int(
        earlier.sum() + own.sum())
    tiles = sum(
        bool(earlier[q:q + block, k:k + block].any())
        or (k <= q and bool((
            episode[q:q + block].max() >= episode[k:k + block].min()
        ) and episode[k:k + block].max() >= episode[q:q + block].min()))
        for q in range(0, L, block) for k in range(0, L, block)
    )
    assert counts_sdar.visible_tiles(model, done[:, 0]) == tiles
    rng = np.random.default_rng(0)
    reveal = np.concatenate(
        [rng.integers(0, S, (D * N, 1)), np.full((D, 1), S)])
    got = counts_sdar.counts(model, done, reveal)
    assert got["blockdiff_rows"] == C * L
    assert got["blockdiff_masked_inputs"] == sum(
        int((reveal[:D * N] >= tau).sum()) + D for tau in range(S))
    assert got["blockdiff_pairs"] == 3 * counts_sdar.pairs(model, done[:, 0])


def test_end_to_end_line():
    proc = run_cell("tiny_sdar_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"learner_env_steps_per_s", "learner_step_ms_p95",
            "setup_s"} == set(line["metrics"])
    for name in ("attention_backend_differs", "moe_overflow", "moe_spills",
                 "compiles_in_window", "steps_not_applied", "grad_leaf_gap",
                 "change_leaf_gap", "loss_gap_first", "loss_gap_later",
                 "sdar_counts_differ"):
        assert f"[compare] {name} = " in proc.stdout
    for tag in ("[sdar] of the last step: blockdiff_rows 288, ",
                "blockdiff_scored_tokens 92, blockdiff_steps 46, ",
                "[tokens] 92 token-actions in 46 steps: ",
                "[balance] seed", "[moe] moe_assignments_held ",
                "by backend: {'dense': ",
                "[mtp] mtp_loss by step: program [0.0, 0.0, 0.0]"):
        assert tag in proc.stdout, tag


def test_traced_line_carries_no_device_metric():
    proc = run_cell("tiny_sdar_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # a CPU trace has no device plane: every scope reader found nothing;
    # the expert layers' load is a counter of the step and is read (the
    # rehearsal's manifest leaves lm_blockdiff.mfu out: a share of a
    # chip's peak has no reading on a CPU)
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_sound_seeds_pass():
    import jax

    cell = load("workloads", "tiny_sdar_learner")
    cfg = load("configs", "tiny_sdar")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound


@pytest.mark.parametrize("fault", [
    "own_block_clean", "local_causal", "wrong_copy", "clip_per_token",
    "no_qk_norm"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_sdar_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is False, proc.stdout[-2000:]
    assert [ln for ln in proc.stdout.splitlines() if "NOT OK" in ln]


def test_the_sound_program_under_the_same_wrapper_is_correct():
    proc = subprocess.run(
        [sys.executable, BROKEN, "none", "--workload", "tiny_sdar_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
