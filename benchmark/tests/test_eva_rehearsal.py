"""The cell of the decoder with chunk-summary attention: its driver end to
end on the CPU at a tiny size, through ``run.py`` under a manifest of its
own (``rehearsal_eva/``), ``correct`` false where it should be, and what
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

MANIFEST = os.path.join(TESTS, "rehearsal_eva", "BENCHMARK.json")
BROKEN = os.path.join(TESTS, "broken_eva_run.py")
CELL = "evabyte_learner_16k"
NEW_METRICS = ["eva.core_roofline_share", "eva.summary_device_share",
               "lm_eva.mfu"]
APPENDED_TO = ["learner.device_ms_per_step", "device.idle_share.learner",
               "device.peak_hbm_gb", "lm.head_loss_device_share",
               "vtrace.device_ms_per_step", "mlp.dense_shared_device_share"]


def load(kind, name):
    with open(os.path.join(TESTS, "rehearsal_eva", "benchmark", kind,
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
        assert entry["unit"] == "%"
        assert callable(test_harness.bench_run.load_reader(name))
    assert per_layer["eva.core_roofline_share"]["layer"] == "kernels"
    assert per_layer["eva.summary_device_share"]["layer"] == "learner step"
    assert per_layer["lm_eva.mfu"]["source"] == "host_clock"
    for name in APPENDED_TO:
        assert per_layer[name]["workloads"][-1] == CELL
    # of expert layers, of latent attention, or counted for other
    # descriptions: a model without them is not listed there
    for name in names:
        if name.startswith(("moe.", "mla.", "mhc.", "mtp.", "loop.")) or (
                name in ("lm.mfu", "lm_latent.mfu", "learner.mfu",
                         "attention.core_roofline_share",
                         "lm.step_roofline_share")):
            assert CELL not in per_layer[name]["workloads"], name


def test_the_benchmarks_cell_and_configuration():
    m = manifest()
    entry = m["workloads"][-1]
    assert (entry["name"], entry["config"], entry["chips"],
            entry["traffic"]) == (
        CELL, "evabyte_pp8", 1, "resident_b1_t16383_done8k")
    e2e = {e["name"] for e in m["end_to_end"]
           if CELL in e.get("workloads", [CELL])}
    assert e2e == {"learner_env_steps_per_s", "learner_step_ms_p95",
                   "setup_s"}
    config_entry = m["configs"][-1]
    assert config_entry["name"] == "evabyte_pp8"
    assert config_entry["reduced"] == ["num_hidden_layers"]
    assert config_entry["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json")
    with open(os.path.join(REPO, config_entry["file"])) as f:
        config = json.load(f)
    # every key of the catalog's config under the same key, but the depth
    published = {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True,
        "max_position_embeddings": 32768, "max_seq_length": 32768,
        "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_chunks": None, "num_hidden_layers": 32,
        "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048,
    }
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] == 4
    assert config["assumed"] and config["deployment"]
    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    assert (cell["unroll_length"], cell["batch_per_chip"], cell["done_rate"],
            cell["in_flight"], cell["check_steps"], cell["warmup_steps"],
            cell["trace_seconds"], cell["attention_backend"],
            cell["driver"]) == (
        16383, 1, 1 / 8192, 2, 3, 3, 3, "flash", "lm_eva_learner_step")
    assert set(cell["limits"]) == {
        "loss_gap_first", "loss_gap_later", "grad_leaf_gap",
        "change_leaf_gap"}
    assert cell["limits_why"] and len(cell["why"]) > 200


def test_the_cells_tile_count_is_the_commonest_at_its_rate():
    """Of 300 draws at the cell's rate that hold a boundary, no count of
    tiles comes up more often than the one the cell fixes; it is fewer
    than every tile there is, so an episode hides whole tiles of every
    seed's step; and a seed's batch is such a draw."""
    import numpy as np

    from benchmark.lib import counts_eva, seeded_eva

    with open(os.path.join(
            REPO, "benchmark", "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    with open(os.path.join(
            REPO, "benchmark", "configs", "evabyte_pp8.json")) as f:
        model = json.load(f)["model"]["kwargs"]
    rng = np.random.default_rng(0)
    seen = collections.Counter()
    while sum(seen.values()) < 300:
        done = rng.random((cell["unroll_length"] + 1, 1)) < cell["done_rate"]
        if done[1:].any():
            seen[counts_eva.visible_tiles(done, model)] += 1
    assert seen.most_common(1)[0][0] == cell["attention_tiles"] == 4 * 117
    assert cell["attention_tiles"] < 4 * 120  # every tile there is
    for seed in (3, 2 ** 31 + 77):
        done = seeded_eva.draw_done(
            seed, (cell["unroll_length"] + 1, 1), cell["done_rate"], model,
            cell["attention_tiles"],
        )
        assert done[1:].any()
        assert counts_eva.visible_tiles(done, model) == 4 * 117


def test_a_draw_held_to_its_tiles_holds_a_boundary_in_every_column():
    """At a tiny size where most draws with every tile hold no boundary:
    the draw held to that count still has one, in both columns."""
    import numpy as np

    from benchmark.lib import counts_eva, seeded_eva

    model = load("configs", "tiny_eva")["model"]["kwargs"]
    shape, rate = (32, 2), 0.02
    every = counts_eva.visible_tiles(np.zeros(shape, bool), model)
    free = np.random.default_rng([1, 2, 0]).random(shape) < rate
    assert not free[1:].any()  # what the draw would have been
    done = seeded_eva.draw_done(1, shape, rate, model, every)
    assert done[1:].any(axis=0).all()
    assert counts_eva.visible_tiles(done, model) == every
    assert (seeded_eva.draw_done(1, shape, rate, model, None) == free).all()


def test_the_follower_frees_a_gradient_nothing_reads_again():
    """The reference's follower deletes a step's gradient once its update
    is dispatched (room for the next step's program at the cell's size),
    behind ``lib/reference_latent.py``'s back, which still holds the first
    one in a list: every leaf of every step's gradient is gone after the
    update, reading one raises, and the follower's numbers come out whole
    all the same, so nothing did read one. (On the CPU nothing is donated:
    the deletion is the follower's own.)"""
    import jax
    import numpy as np

    cell = load("workloads", "tiny_eva_learner")
    cfg = load("configs", "tiny_eva")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    c = driver.calibration(cell, cfg, jax.devices()[:1]).c
    follower = c.follower("float32")
    update, freed = follower._update, []

    def spy(params, nu, g, scale):
        out = update(params, nu, g, scale)
        freed.append(jax.tree_util.tree_leaves(g))
        return out

    follower._update = spy
    batch = c.batch(13)
    out = c.reference(13, batch, None)
    assert len(freed) == cell["check_steps"]
    assert all(leaf.is_deleted() for leaves in freed for leaf in leaves)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(freed[0][0])
    assert len(out["losses"]) == cell["check_steps"]
    assert np.isfinite(out["losses"]).all()
    assert np.isfinite(out["change_norms"]).all() and min(
        out["change_norms"]) > 0
    assert all(np.isfinite(g).all() for g in out["grad_abs"])
    # and against a kept gradient, the path a whole run takes
    again = c.reference(13, batch, out["grad_abs"])
    assert max(again["grad_diff_norms"]) <= 1e-6 * max(again["grad_norms"])


def test_end_to_end_line():
    proc = run_cell("tiny_eva_learner", manifest=MANIFEST, seed=2 ** 31 + 5)
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
    for tag in ("[eva] of the last step, every block: eva_local_pairs ",
                "eva_summary_pairs ", "eva_chunks_cut ", "mtp_positions ",
                "[moe] moe_assignments_held 0,", "[host] peak resident",
                "[stalls] longest gap", "by backend: {'dense': 4}",
                "[mtp] mtp_loss by step: program ["):
        assert tag in proc.stdout, tag
    assert "[balance]" not in proc.stdout
    # the step's own count of its pairs is the benchmark's from the batch
    eva = next(ln for ln in proc.stdout.splitlines()
               if ln.startswith("[eva]"))
    counted = eval(eva.split("counted from the boundaries: ")[1])
    for key in ("local_pairs", "summary_pairs", "chunks_cut"):
        assert f"eva_{key} {counted[key]}," in eva, eva


def test_traced_line_carries_no_device_metric():
    proc = run_cell("tiny_eva_learner", manifest=MANIFEST, trace=1,
                    seconds=2)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    # a CPU trace has no device plane: every scope reader, the two new
    # ones among them, found nothing and said nothing
    assert set(line["metrics"]) == set()
    assert "[scopes] device seconds in the traced window" in proc.stdout


def test_control_fails_and_sound_passes():
    import jax

    from benchmark.lib import reference_train

    cell = load("workloads", "tiny_eva_learner")
    cfg = load("configs", "tiny_eva")
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    handle = driver.calibration(cell, cfg, jax.devices()[:1])
    control = reference_train.CONTROL_OF[cfg["precision"]]
    for seed in (11, 2 ** 31 + 12):
        sound = handle.sound(seed)
        assert all(sound[k] <= cell["limits"][k] for k in sound), sound
        low = handle.control(seed, control)
        assert [k for k in low if low[k] > cell["limits"][k]], (seed, low)


@pytest.mark.parametrize("fault", ["no_summaries", "no_mu", "mu_on_value",
                                   "mean_pooling", "sliding_window",
                                   "step_keeps_state", "wrong_direction"])
def test_a_whole_run_over_a_broken_program_is_not_correct(fault):
    proc = subprocess.run(
        [sys.executable, BROKEN, fault, "--workload", "tiny_eva_learner",
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
        [sys.executable, BROKEN, "none", "--workload", "tiny_eva_learner",
         "--seed", "5", "--seconds", "1.5", "--trace", "0", "--manifest",
         MANIFEST],
        cwd=REPO, env=cpu_env(), capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert last_line(proc)["correct"] is True
