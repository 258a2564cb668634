"""The latent-attention cell's driver end to end on the CPU at a tiny
size, through ``run.py`` under a manifest of its own
(``rehearsal_latent/``), ``correct`` false where it should be, and what
``BENCHMARK.json`` says of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_latent", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_latent_run.py")
CELL = "glm47_learner_8k"
NEW_METRICS = ["mla.core_roofline_share", "mla.proj_device_share",
               "mlp.dense_shared_device_share", "mtp.device_share",
               "lm_latent.mfu"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "moe.device_share",
               "moe.dispatch_device_share", "moe.load_max_over_mean",
               "lm.head_loss_device_share", "vtrace.device_ms_per_step"]


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_latent", "benchmark", kind,
                           name + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_its_manifest_keeps_to_the_contract():
    test_harness.test_manifest_keeps_to_the_contract(MANIFEST)


def test_every_new_metric_has_an_entry_and_a_reader():
    per_layer = {m["name"]: m for m in manifest()["per_layer"]}
    # found by name, in the issue's order and together; not by their
    # place at the end, which the next PR's entries take
    names = list(per_layer)
    at = [names.index(name) for name in NEW_METRICS]
    assert at == list(range(at[0], at[0] + len(at)))
    for name in NEW_METRICS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "learner_env_steps_per_s"
        assert os.path.exists(test_harness.bench_run.reader_path(name))
        assert callable(test_harness.bench_run.load_reader(name))
    assert per_layer["mla.core_roofline_share"]["unit"] == "%"
    for name in APPENDED_TO:
        assert CELL in per_layer[name]["workloads"]
    # counted for the other decoder's description: not this cell's
    for name in ("attention.core_roofline_share", "lm.mfu",
                 "lm.step_roofline_share", "moe.experts_roofline_share"):
        assert CELL not in per_layer[name]["workloads"]


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == ("glm47_flash_share8", 1)
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = next(
        c for c in m["configs"] if c["name"] == "glm47_flash_share8")
    assert config_entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    with open(os.path.join(REPO, config_entry["file"])) as f:
        config = json.load(f)
    # every number of the catalog's config under the same key, but the
    # three reduced
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
    }
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    assert config["assumed"] and config["deployment"]
    cell = json.load(open(os.path.join(
        REPO, "benchmark", "workloads", CELL + ".json")))
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"]) == (8191, 1, 0.00025, 2, 3, 3, 3)
    kw = config["model"]["kwargs"]
    assert kw["moe_buffer_rows"] == int(2.5 * 8192 * 4 * 8 / 64)


def test_end_to_end_line():
    proc = run_cell("tiny_latent_learner", manifest=MANIFEST,
                    seed=2 ** 31 + 5)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert set(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert {"learner_env_steps_per_s", "learner_step_ms_p95",
            "setup_s"} == set(line["metrics"])
    for name in ("attention_backend_differs", "moe_overflow",
                 "compiles_in_window", "steps_not_applied", "grad_leaf_gap",
                 "change_leaf_gap", "loss_gap_first", "loss_gap_later"):
        assert f"[compare] {name} = " in proc.stdout
    for tag in ("[moe] ", "[mtp] mtp_loss by step", "[host] peak resident",
                "[balance] seed", "[stalls] longest gap"):
        assert tag in proc.stdout


def test_traced_line_carries_the_counters_and_no_device_metric():
    proc = run_cell("tiny_latent_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # the program's counter is read on any platform; a CPU trace has no
    # device plane, so every scope reader found nothing and said nothing
    # (lm_latent.mfu is a share of a chip's peak and a CPU has none in
    # lib/peaks.py: the rehearsal's manifest leaves it out)
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_control_fails_and_sound_passes():
    import jax

    from benchmark.lib import reference_train

    cell = load("workloads", "tiny_latent_learner")
    cfg = load("configs", "tiny_latent")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        assert [k for k in low if low[k] > cell["limits"][k]], (seed, low)


@pytest.mark.parametrize("fault", ["no_bias", "no_scale", "softmax",
                                   "no_shared", "no_mtp_term", "late_key"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_latent_learner",
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
        [sys.executable, BROKEN, "none", "--workload", "tiny_latent_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
