"""The cell of the decoder on several residual streams: its driver end to
end on the CPU at a tiny size, through ``run.py`` under a manifest of its
own (``rehearsal_mhc/``), ``correct`` false where it should be, and what
``BENCHMARK.json`` says of the cell."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from helpers import REPO, RESULT_KEYS, TESTS, cpu_env, last_line, run_cell
import test_harness

MANIFEST = os.path.join(TESTS, "rehearsal_mhc", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_mhc_run.py")
CELL = "xing4_learner_4k"
NEW_METRICS = ["mhc.device_share", "mhc.stream_roofline_share"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "moe.device_share",
               "moe.dispatch_device_share", "moe.load_max_over_mean",
               "lm.head_loss_device_share", "vtrace.device_ms_per_step",
               "mla.core_roofline_share", "mla.proj_device_share",
               "mlp.dense_shared_device_share", "lm_latent.mfu"]


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_mhc", "benchmark", kind,
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
    assert at == list(range(at[0], at[0] + len(at)))
    for name in NEW_METRICS:
        entry = per_layer[name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "learner_env_steps_per_s"
        assert entry["unit"] == "%" and entry["source"] == "device_trace"
        assert callable(test_harness.bench_run.load_reader(name))
    assert per_layer["mhc.device_share"]["layer"] == "learner step"
    assert per_layer["mhc.stream_roofline_share"]["layer"] == "kernels"
    for name in APPENDED_TO:
        assert per_layer[name]["workloads"][-1] == CELL
    # what test_latent_rehearsal.py's check of the same name holds of the
    # other latent cell's five metrics, with "is listed" for "is alone":
    # this cell was appended to four of them, which that assertion (a
    # file of the benchmark's, not this PR's to edit) now fails on
    for name in ("mla.core_roofline_share", "mla.proj_device_share",
                 "mlp.dense_shared_device_share", "mtp.device_share",
                 "lm_latent.mfu"):
        assert per_layer[name]["workloads"][0] == "glm47_learner_8k"
        assert per_layer[name]["moves"] == "learner_env_steps_per_s"
        assert callable(test_harness.bench_run.load_reader(name))
    # counted for other descriptions, or of a module this model lacks
    for name in ("attention.core_roofline_share", "lm.mfu",
                 "lm.step_roofline_share", "moe.experts_roofline_share",
                 "mtp.device_share"):
        assert CELL not in per_layer[name]["workloads"]


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = next(w for w in m["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"], entry["traffic"]) == (
        "xing4_share8", 1, "resident_b1_t4095_done2k")
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = next(c for c in m["configs"] if c["name"] == "xing4_share8")
    assert config_entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert config_entry["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")
    with open(os.path.join(REPO, config_entry["file"])) as f:
        config = json.load(f)
    # every number of the catalog's config under the same key, but the
    # four reduced
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    }
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
        5, 8, 16384, 0)
    assert config["assumed"] and config["deployment"]
    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"], cell["attention_backend"]) == (
        4095, 1, 0.0005, 2, 3, 3, 3, "flash")
    # the four gaps the decoder cells use, each held (the driver's
    # comparison raises on a cell that leaves one out)
    assert set(cell["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_leaf_gap",
        "change_leaf_gap"}


def test_the_cells_tile_count_is_the_commonest_at_its_rate():
    """Of 400 draws at the cell's rate, no count of tiles comes up more
    often than the one the cell fixes."""
    import collections

    import numpy as np

    from benchmark.lib import seeded_latent, seeded_lm

    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            REPO, "benchmark", "configs", "xing4_share8.json")) as f:
        model = seeded_latent.expanded_model(json.load(f)["model"]["kwargs"])
    rng = np.random.default_rng(0)
    seen = collections.Counter(
        seeded_lm.attention_tiles(
            rng.random((cell["unroll_length"] + 1, 1)) < cell["done_rate"],
            model)
        for _ in range(400)
    )
    assert seen.most_common(1)[0][0] == cell["attention_tiles"] == 5 * 36


def test_end_to_end_line():
    proc = run_cell("tiny_mhc_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
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
    for tag in ("[moe] ", "[mhc] of the last step's remix matrices",
                "hc_row_sum_gap", "hc_col_sum_gap", "hc_res_clamped 0",
                "[host] peak resident", "[balance] seed",
                "[stalls] longest gap",
                "[mtp] mtp_loss by step: program [0.0, 0.0, 0.0], "
                "reference [0.0, 0.0, 0.0]"):
        assert tag in proc.stdout


def test_traced_line_carries_the_counters_and_no_device_metric():
    proc = run_cell("tiny_mhc_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # a CPU trace has no device plane: every scope reader, the two new
    # ones among them, found nothing and said nothing
    assert set(line["metrics"]) == {"moe.load_max_over_mean"}
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_control_fails_and_sound_passes():
    import jax

    from benchmark.lib import reference_train

    cell = load("workloads", "tiny_mhc_learner")
    cfg = load("configs", "tiny_mhc")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        assert [k for k in low if low[k] > cell["limits"][k]], (seed, low)


@pytest.mark.parametrize("fault", ["no_iterations", "one_iteration",
                                   "post_gate", "no_score_scale",
                                   "wide_value", "ascent",
                                   "step_keeps_state"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_mhc_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is False, proc.stdout[-2000:]
    failed = [ln.split()[1] for ln in proc.stdout.splitlines()
              if "NOT OK" in ln]
    assert failed
    if fault == "ascent":
        # every leaf's first change has the reference's norm: the later
        # steps' loss is the gap that has to see the sign
        assert "loss_gap_later" in failed and "grad_leaf_gap" not in failed


def test_a_cell_that_leaves_a_gap_out_of_its_limits_is_refused(tmp_path):
    """The comparison holds all four gaps: a workload file without one of
    them ends the run with an error, not with a shorter ``correct``."""
    import shutil

    root = tmp_path / "rehearsal_mhc"
    shutil.copytree(os.path.dirname(MANIFEST), root)
    path = root / "benchmark" / "workloads" / "tiny_mhc_learner.json"
    cell = json.loads(path.read_text())
    del cell["limits"]["loss_gap_later"]
    path.write_text(json.dumps(cell))
    proc = run_cell("tiny_mhc_learner",
                    manifest=str(root / "BENCHMARK.json"))
    assert proc.returncode != 0
    assert "KeyError: 'loss_gap_later'" in proc.stderr


def test_the_sound_program_under_the_same_wrapper_is_correct():
    proc = subprocess.run(
        [sys.executable, BROKEN, "none", "--workload", "tiny_mhc_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
