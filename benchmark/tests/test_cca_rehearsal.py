"""The cell of the decoder with compressed convolutional attention and
top-1 experts behind an MLP router: its driver end to end on the CPU at a
tiny size, through ``run.py`` under a manifest of its own
(``rehearsal_cca/``), ``correct`` false where it should be, and what
``BENCHMARK.json`` says of the cell."""

import collections
import importlib
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_cca", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_cca_run.py")
CELL, CONFIG = "zaya1_learner_8k", "zaya1_share8"
NEW_METRICS = ["cca.mix_device_share", "cca.mix_roofline_share",
               "cca.core_roofline_share", "moe.router_device_share",
               "lm_cca.mfu", "residual_scale.device_share"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "moe.device_share",
               "moe.dispatch_device_share", "moe.load_max_over_mean",
               "lm.head_loss_device_share", "vtrace.device_ms_per_step",
               "moe.experts_roofline_share"]


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_cca", "benchmark", kind,
                           name + ".json")) as f:
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
    assert per_layer["cca.mix_roofline_share"]["layer"] == "kernels"
    assert per_layer["cca.core_roofline_share"]["layer"] == "kernels"
    assert per_layer["cca.mix_device_share"]["layer"] == "learner step"
    assert per_layer["moe.router_device_share"]["layer"] == "expert layer"
    assert per_layer["lm_cca.mfu"]["source"] == "host_clock"
    assert per_layer["residual_scale.device_share"]["layer"] == "learner step"
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    # of latent attention, of the stream mixing, of a prediction module,
    # of the delta rule, or counted for other descriptions: not this
    # model's
    for name in names:
        if name.startswith(("mla.", "mhc.", "mtp.", "eva.", "kda.", "loop.",
                            "mlp.")) or (
                name in ("lm.mfu", "lm_latent.mfu", "lm_eva.mfu",
                         "lm_kda.mfu", "learner.mfu",
                         "attention.core_roofline_share",
                         "lm.step_roofline_share")):
            assert CELL not in per_layer[name]["workloads"], name


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["name"], entry["config"], entry["chips"],
            entry["traffic"]) == (CELL, CONFIG, 1, "resident_b1_t8191_done2k")
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert config_entry["source"] == (
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json")
    with open(os.path.join(REPO, config_entry["file"])) as f:
        config = json.load(f)
    assert config_entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["assumed"] and config["deployment"]
    assert config["reference"] == CONFIG
    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"], cell["attention_backend"],
            cell["driver"]) == (
        8191, 1, 1 / 2048, 2, 3, 3, 3, "flash", "lm_cca_learner_step")
    assert config["model"]["kwargs"]["moe_buffer_rows"] == 4608 == 18 * 256
    assert set(cell["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_leaf_gap",
        "change_leaf_gap"}
    assert cell["limits_why"] and len(cell["why"]) > 200


def test_the_cells_tile_count_is_the_commonest_among_draws_with_a_boundary():
    """The count the cell fixes is the commonest of 29,473 draws at the
    cell's rate that hold a boundary and none at the first position (53
    tiles a layer in 3.9% of them, the next, 59, in 3.5%: my count, PR
    46); the distribution is flat, so of this test's 3,000 first draws it
    is among the five commonest and within a fifth of the first. A seed's
    batch is such a draw."""
    import numpy as np

    from benchmark.lib import counts_cca, seeded_kda, seeded_lm

    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        model = json.load(f)["model"]["kwargs"]
    one = dict(model, layers=[{"attention": "cca", "mlp": "sparse"}])
    shape = (cell["unroll_length"] + 1, 1)
    seen = collections.Counter()
    for s in range(3000):
        done = np.random.default_rng([s, 2, 0]).random(shape) < cell[
            "done_rate"]
        if done.any() and not done[0].any():
            seen[seeded_lm.attention_tiles(done, one)] += 1
    assert cell["attention_tiles"] == 265
    top = seen.most_common(5)
    assert 53 in [count for count, _ in top]
    assert seen[53] > 0.8 * top[0][1]
    assert len(counts_cca.expanded(model)["layers"]) == 5
    for seed in (3, 2 ** 31 + 77):
        done = seeded_kda.draw_done(
            seed, shape, cell["done_rate"], model, cell["attention_tiles"])
        assert not done[0].any() and done.any()
        assert seeded_lm.attention_tiles(
            done, counts_cca.expanded(model)) == 265


def test_the_labelling_seats_the_mean_load_and_leaves_the_skip_last():
    import numpy as np

    from benchmark.lib import seeded_cca

    rng = np.random.default_rng(0)
    loads = rng.integers(100, 900, size=17)
    perm = seeded_cca.held_first(loads, (0, 8), 1, rng)
    assert sorted(perm) == list(range(17)) and perm[16] == 16
    held = loads[perm][:8].sum()
    assert abs(held - loads.sum() * 8 / 17) < 0.02 * loads.sum()
    # another share of the same router: the same experts, seated there
    later = seeded_cca.held_first(loads, (8, 8), 1, np.random.default_rng(0))
    assert later[16] == 16 and sorted(later) == list(range(17))


def test_end_to_end_line():
    proc = run_cell("tiny_cca_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
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
                 "zaya_counts_differ"):
        assert f"[compare] {name} = " in proc.stdout
    for tag in ("[zaya] of the last step, every layer: cca_taps_cut ",
                "moe_tokens_skipped ", "moe_gate_mean 0.", "router_state_rms ",
                "[balance] seed", "[moe] moe_assignments_held ",
                "by backend: {'dense': ",
                "[mtp] mtp_loss by step: program [0.0, 0.0, 0.0]"):
        assert tag in proc.stdout, tag
    # the step's own count of its cut taps is the benchmark's from done
    zaya = next(ln for ln in proc.stdout.splitlines()
                if ln.startswith("[zaya]"))
    counted = int(zaya.split("counted from the boundaries ")[1].split(";")[0])
    assert counted > 15 and f"cca_taps_cut {counted}," in zaya, zaya


def test_traced_line_carries_no_device_metric():
    proc = run_cell("tiny_cca_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # a CPU trace has no device plane: every scope reader found nothing;
    # the expert layers' load is a counter of the step and is read
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_sound_seeds_pass():
    import jax

    cell = load("workloads", "tiny_cca_learner")
    cfg = load("configs", "tiny_cca")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound


@pytest.mark.parametrize("fault", [
    "tap_across_boundary", "values_not_shifted", "gate_renormalised",
    "router_state_dropped", "skip_runs_expert_0", "head_untied"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_cca_learner",
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
        [sys.executable, BROKEN, "none", "--workload", "tiny_cca_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
