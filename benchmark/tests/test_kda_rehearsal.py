"""The cell of the decoder with delta-rule blocks and a carried state: its
driver end to end on the CPU at a tiny size, through ``run.py`` under a
manifest of its own (``rehearsal_kda/``), ``correct`` false where it
should be, and what ``BENCHMARK.json`` says of the cell."""

import collections
import importlib
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_kda", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_kda_run.py")
CELL, CONFIG = "solar2_learner_4k", "solar_open2_share8"
NEW_METRICS = ["kda.core_device_share", "kda.proj_device_share",
               "kda.core_roofline_share", "lm_kda.mfu"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "moe.device_share",
               "moe.dispatch_device_share", "moe.load_max_over_mean",
               "lm.head_loss_device_share", "vtrace.device_ms_per_step",
               "mlp.dense_shared_device_share"]


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_kda", "benchmark", kind,
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
    assert per_layer["kda.core_roofline_share"]["layer"] == "kernels"
    assert per_layer["kda.core_device_share"]["layer"] == "learner step"
    assert per_layer["lm_kda.mfu"]["source"] == "host_clock"
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    # of latent attention, of the stream mixing, of a prediction module,
    # or counted for other descriptions: not this model's
    for name in names:
        if name.startswith(("mla.", "mhc.", "mtp.", "eva.", "loop.")) or (
                name in ("lm.mfu", "lm_latent.mfu", "lm_eva.mfu",
                         "learner.mfu", "attention.core_roofline_share",
                         "moe.experts_roofline_share",
                         "lm.step_roofline_share")):
            assert CELL not in per_layer[name]["workloads"], name


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["name"], entry["config"], entry["chips"],
            entry["traffic"]) == (
        CELL, CONFIG, 1, "resident_b1_t4095_done512_state")
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert config_entry["source"] == (
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json")
    with open(os.path.join(REPO, config_entry["file"])) as f:
        config = json.load(f)
    assert config_entry["reduced"] == config["reduced"]
    assert config["assumed"] and config["deployment"]
    assert config["reference"] == CONFIG
    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"], cell["attention_backend"],
            cell["recurrent_path"], cell["driver"]) == (
        4095, 1, 1 / 512, 2, 3, 3, 3, "flash", "chunked",
        "lm_kda_learner_step")
    assert set(cell["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_leaf_gap",
        "change_leaf_gap"}
    assert cell["limits_why"] and len(cell["why"]) > 200


def test_the_cells_tile_count_is_the_commonest_at_its_rate():
    """Of 3,000 draws at the cell's rate no count of the softmax layer's
    tiles comes up more often than the one the cell fixes; a seed's batch
    is such a draw, and its first position continues the state handed
    in."""
    import numpy as np

    from benchmark.lib import seeded_kda, seeded_lm

    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            REPO, "benchmark", "configs", CONFIG + ".json")) as f:
        model = json.load(f)["model"]["kwargs"]
    counted = seeded_kda.softmax_layers(model)
    assert len(counted["layers"]) == 1  # three of four blocks run no kernel
    shape = (cell["unroll_length"] + 1, 1)
    seen = collections.Counter(
        seeded_lm.attention_tiles(
            np.random.default_rng([s, 2, 0]).random(shape)
            < cell["done_rate"], counted)
        for s in range(3000))  # every seed's first draw: 16 in 23%, 17 in 21%
    assert seen.most_common(1)[0][0] == cell["attention_tiles"] == 16
    for seed in (3, 2 ** 31 + 77):
        done = seeded_kda.draw_done(
            seed, shape, cell["done_rate"], model, cell["attention_tiles"])
        assert not done[0].any() and done.any()
        assert seeded_lm.attention_tiles(done, counted) == 16


def test_the_batch_hands_in_a_seeded_state():
    import jax
    import numpy as np

    cell = load("workloads", "tiny_kda_learner")
    cfg = load("configs", "tiny_kda")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    c = driver.calibration(cell, cfg, jax.devices()[:1]).c
    batch, again, other = c.batch(13), c.batch(13), c.batch(14)
    state = batch["core_state"]
    assert [s.shape for s in state] == [(1, 3, 2, 16, 16), (1, 3, 3, 96)]
    assert abs(float(np.std(state[0])) - cfg["seeding"]["state_scale"]) < 0.02
    assert abs(float(np.std(state[1])) - cfg["seeding"]["rows_scale"]) < 0.2
    for a, b, d in zip(state, again["core_state"], other["core_state"]):
        assert np.array_equal(a, b) and not np.array_equal(a, d)
    assert sum(s.size for s in state) == cfg["core_state_size"]


def test_end_to_end_line():
    proc = run_cell("tiny_kda_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"learner_env_steps_per_s", "learner_step_ms_p95",
            "setup_s"} == set(line["metrics"])
    for name in ("attention_backend_differs", "moe_overflow",
                 "compiles_in_window", "steps_not_applied", "grad_leaf_gap",
                 "change_leaf_gap", "loss_gap_first", "loss_gap_later",
                 "recurrent_path_differs", "kda_counts_differ"):
        assert f"[compare] {name} = " in proc.stdout
    for tag in ("[kda] of the last step, every block: kda_state_resets ",
                "kda_chunks_cut ", "kda_log_decay_min -", "kda_state_rms ",
                "[balance] seed", "[moe] moe_assignments_held ",
                "[recurrent] calls traced into the step, by path: "
                "{'chunked': 2}", "by backend: {'dense': 1}",
                "[mtp] mtp_loss by step: program [0.0, 0.0, 0.0]"):
        assert tag in proc.stdout, tag
    # the step's own count of its boundaries is the benchmark's from done
    kda = next(ln for ln in proc.stdout.splitlines()
               if ln.startswith("[kda]"))
    counted = eval(kda.split("counted from the boundaries: ")[1])
    assert counted["kda_state_resets"] > 0
    for key, value in counted.items():
        assert f"{key} {value}," in kda, kda


def test_traced_line_carries_no_device_metric():
    proc = run_cell("tiny_kda_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # a CPU trace has no device plane: every scope reader found nothing;
    # the expert layers' load is a counter of the step and is read
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_control_fails_and_sound_passes():
    import jax

    from benchmark.lib import reference_train

    cell = load("workloads", "tiny_kda_learner")
    cfg = load("configs", "tiny_kda")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        assert [k for k in low if low[k] > cell["limits"][k]], (seed, low)


@pytest.mark.parametrize("fault", ["no_reset", "state_ignored",
                                   "beta_not_doubled", "step_keeps_state"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_kda_learner",
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
        [sys.executable, BROKEN, "none", "--workload", "tiny_kda_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
