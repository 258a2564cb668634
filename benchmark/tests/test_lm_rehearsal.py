"""The language-model driver end to end on the CPU at a tiny size, through
``run.py`` under a manifest of its own (``rehearsal_lm/``: the rehearsal's
own manifest is not edited), and ``correct`` false where it should be."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_lm", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_lm_run.py")


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_lm", "benchmark", kind,
                           name + ".json")) as f:
        return json.load(f)


def test_its_manifest_keeps_to_the_contract():
    test_harness.test_manifest_keeps_to_the_contract(MANIFEST)


def test_the_benchmarks_cell_lists_what_its_driver_reports():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = "mellum2_learner_8k"
    entry = next(w for w in m["workloads"] if w["name"] == cell)
    assert (entry["config"], entry["chips"]) == ("mellum2_share8", 1)
    e2e = {e["name"] for e in m["end_to_end"]
           if cell in e.get("workloads", [cell])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    layer = {p["name"] for p in m["per_layer"] if cell in p["workloads"]}
    assert {"moe.experts_roofline_share", "attention.core_roofline_share",
            "moe.device_share", "moe.dispatch_device_share",
            "lm.head_loss_device_share", "vtrace.device_ms_per_step",
            "moe.load_max_over_mean", "lm.mfu"} <= layer
    assert not {"learner.mfu", "kernels.step_roofline_share"} & layer
    with open(os.path.join(REPO, "benchmark/configs/mellum2_share8.json")) as f:
        config = json.load(f)
    kw = config["model"]["kwargs"]
    # every width as published, the share where the file says it is one
    assert (config["hidden_size"], config["head_dim"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["sliding_window"]) == (2304, 128, 896, 8, 1024)
    assert (kw["hidden_size"], kw["head_dim"], kw["moe_intermediate_size"],
            kw["top_k"], kw["num_experts"]) == (2304, 128, 896, 8, 64)
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_attention_heads",
        "num_key_value_heads", "vocab_size"}
    assert (len(kw["layers"]), kw["experts_held"], kw["num_heads"],
            kw["num_kv_heads"], kw["vocab_size"]) == (8, [0, 8], 4, 1, 12288)
    assert [l["attention"] for l in kw["layers"]] == [
        "sliding", "sliding", "sliding", "full"] * 2


def test_earlier_metrics_keep_their_entries_and_readers():
    """What ``test_spans.py`` checks of PR 24's seven metrics, found by name
    and not by their place at the end of the list (``benchmark/conftest.py``
    says why)."""
    import test_spans

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    names = list(per_layer)
    at = [names.index(name) for name in test_spans.NEW_METRICS]
    assert at == list(range(at[0], at[0] + len(at)))  # in the issue's order
    for name in test_spans.NEW_METRICS:
        assert per_layer[name]["workloads"] == ["atari_loop"]
        assert per_layer[name]["moves"] == "loop_env_steps_per_s"
        assert os.path.exists(test_harness.bench_run.reader_path(name))


def test_end_to_end_line():
    proc = run_cell("tiny_lm_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"learner_env_steps_per_s", "learner_step_ms_p95",
            "setup_s"} == set(line["metrics"])
    for name in ("attention_backend_differs", "moe_overflow",
                 "compiles_in_window", "steps_not_applied", "grad_leaf_gap"):
        assert f"[compare] {name} = " in proc.stdout
    assert "[moe] held " in proc.stdout


def test_traced_line_carries_the_counters_and_no_device_metric():
    proc = run_cell("tiny_lm_learner", manifest=MANIFEST, trace=1, seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # the program's counter is read on any platform; a CPU trace has no
    # device plane, so every scope reader found nothing and said nothing
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0


def test_control_fails_and_sound_passes():
    import jax

    from benchmark.lib import reference_train

    cell, cfg = load("workloads", "tiny_lm_learner"), load("configs", "tiny_lm")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        assert [k for k in low if low[k] > cell["limits"][k]], (seed, low)


@pytest.mark.parametrize("fault,number", [
    ("float8", "grad_leaf_gap"),
    ("skipped_expert", "grad_leaf_gap"),
    ("dropped_window", "grad_leaf_gap"),
    ("dropped_token", "grad_leaf_gap"),
    ("forced_backend", "attention_backend_differs"),
])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault, number):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_lm_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is False, proc.stdout[-2000:]
    bad = [ln for ln in proc.stdout.splitlines() if "NOT OK" in ln]
    assert any(f"[compare] {number} " in ln for ln in bad), bad


def test_the_sound_program_under_the_same_wrapper_is_correct():
    proc = subprocess.run(
        [sys.executable, BROKEN, "none", "--workload", "tiny_lm_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
