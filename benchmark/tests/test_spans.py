"""The readers of the program's phases and spans: each on hand-made
readings, the idle-owner arithmetic on a hand-made trace, and the loop's
rehearsal cell traced on the CPU, where the six metrics that read the
StepScope ledger are on the line and the one that reads the device is
not."""

import json
import os
import sys

import pytest

from benchmark.lib import spans, xplane
from benchmark.lib.xplane import Event

from helpers import BENCH, REHEARSAL, REPO, last_line, run_cell

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

NEW_METRICS = [
    "loop.learn_stage_ms_per_update", "loop.learn_dispatch_ms_per_update",
    "loop.acc_pump_ms_per_update", "loop.metrics_drain_ms_per_update",
    "loop.act_host_share", "loop.unattributed_share",
    "device.idle_owned_share.loop",
]
LEDGER_METRICS = set(NEW_METRICS[:-1])

# Two readings of the loop's StepScope summary, 10 s of wall and four
# updates apart.
BEFORE = {"wall_s": 5.0, "phases": {
    "env_wait": 0.1, "host_sync": 1.0, "grad_allreduce": 0.01,
    "unroll_cat": 0.1, "obs_stage": 0.2, "act_dispatch": 0.3,
    "env_submit": 0.05, "acc_update": 0.02, "learn_batch_get": 0.5,
    "learn_stage": 1.0, "grad_dispatch": 0.05, "grad_result": 0.01,
    "grad_stage": 0.02, "apply_dispatch": 0.03, "metrics_drain": 0.2,
    "log": 0.01, "other": 0.2,
}}
AFTER = {"wall_s": 15.0, "phases": {
    "env_wait": 0.2, "host_sync": 3.0, "grad_allreduce": 0.03,
    "unroll_cat": 0.3, "obs_stage": 0.6, "act_dispatch": 0.9,
    "env_submit": 0.15, "acc_update": 0.06, "learn_batch_get": 1.3,
    "learn_stage": 2.6, "grad_dispatch": 0.13, "grad_result": 0.03,
    "grad_stage": 0.06, "apply_dispatch": 0.07, "metrics_drain": 0.6,
    "log": 0.03, "other": 0.5,
}}
ROWS = ({"updates": 6.0}, {"updates": 10.0})
READINGS = {"stepscope": (BEFORE, AFTER), "rows": ROWS}
# A program older than its spans: the four phases it had, and `other`.
OLD = tuple(
    {"wall_s": s["wall_s"], "phases": {
        k: v for k, v in s["phases"].items()
        if k in ("env_wait", "host_sync", "grad_allreduce", "other")
    }}
    for s in (BEFORE, AFTER)
)


def read(name, readings):
    return bench_run.load_reader(name)(readings, {})


@pytest.mark.parametrize("name,expected", [
    # (0.8 + 1.6 + 0.04) s over 4 updates
    ("loop.learn_stage_ms_per_update", 610.0),
    # (0.08 + 0.04) s over 4 updates
    ("loop.learn_dispatch_ms_per_update", 30.0),
    # (0.04 + 0.02 + 0.02) s over 4 updates
    ("loop.acc_pump_ms_per_update", 20.0),
    ("loop.metrics_drain_ms_per_update", 100.0),
    # (0.2 + 0.4 + 0.6 + 0.1) s of 10 s
    ("loop.act_host_share", 13.0),
    ("loop.unattributed_share", 3.0),
])
def test_ledger_reader_on_hand_made_readings(name, expected):
    assert read(name, READINGS) == pytest.approx(expected)
    # Nothing to read: no readings, a program without these phases, a
    # window without an update.
    assert read(name, {}) is None
    assert read(name, {"stepscope": OLD, "rows": ROWS}) is None
    if name.endswith("_per_update"):
        assert read(name, dict(READINGS, rows=(ROWS[0], ROWS[0]))) is None


def hand_made_trace():
    """3 ms of window. The device works 0-1 ms and 2.5-3 ms, with a
    100 us gap at 0.5 ms; the loop's thread is in `learn_stage` (with
    `metrics_drain` nested at its end) from 0.9 to 2.4 ms, and an
    Accumulator thread converts gradients around the first gap."""
    us = 1e3
    return {
        "/device:TPU:0": {xplane.OPS_LINE: [
            Event("fusion.1", 0, 500 * us), Event("fusion.2", 600 * us, 1000 * us),
            Event("fusion.3", 2500 * us, 3000 * us),
        ]},
        "/host:CPU": {
            "loop": [
                Event("bench.window", 0, 3000 * us),
                Event("moolib.vtrace_learner.step", 0, 3000 * us),
                Event("moolib.vtrace_learner.learn_stage", 900 * us, 2400 * us),
                Event("moolib.vtrace_learner.metrics_drain", 2300 * us, 2400 * us),
            ],
            "rpc": [Event("moolib.acc.grad_to_host", 450 * us, 700 * us)],
        },
    }


def test_idle_owners_on_a_hand_made_trace():
    trace = hand_made_trace()
    # Gaps: 500-600 us (inside grad_to_host only) and 1000-2500 us (1400
    # of its 1500 us inside learn_stage). The whole step owns nothing.
    assert spans.idle_owners(trace) == [
        ["moolib.vtrace_learner.learn_stage", pytest.approx(1500e-6)],
        ["moolib.acc.grad_to_host", pytest.approx(100e-6)],
    ]
    # The same rule as the harness's own, which the sweep must agree with.
    ops = trace["/device:TPU:0"][xplane.OPS_LINE]
    assert xplane.idle_gaps(ops, (0, 3e6), spans.owners_of(trace)) == [
        [name, pytest.approx(s)] for name, s in spans.idle_owners(trace)
    ]
    value = bench_run.load_reader("device.idle_owned_share.loop")(
        {"trace": trace}, {}
    )
    assert value == pytest.approx(100.0)
    # One gap owned, one not: the loop's spans taken away up to 2.4 ms
    # leave the long gap with no owner.
    trace["/host:CPU"]["loop"] = trace["/host:CPU"]["loop"][:2]
    assert spans.idle_owners(trace) == [
        [spans.UNOWNED, pytest.approx(1500e-6)],
        ["moolib.acc.grad_to_host", pytest.approx(100e-6)],
    ]
    assert read("device.idle_owned_share.loop", {"trace": trace}) == (
        pytest.approx(100.0 * 100 / 1600)
    )


def test_of_spans_that_cover_a_gap_alike_the_inner_owns_it():
    inner = Event("moolib.l.inner", 10, 20)
    outer = Event("moolib.l.outer", 0, 100)
    assert spans.gap_owners([(12, 18)], [outer, inner]) == {
        "moolib.l.inner": 6.0
    }
    assert spans.gap_owners([(5, 30)], [inner, outer]) == {
        "moolib.l.outer": 25.0
    }
    assert spans.gap_owners([(200, 300)], [inner, outer]) == {
        spans.UNOWNED: 100.0
    }


def test_idle_owned_share_has_nothing_to_read(capsys):
    trace = hand_made_trace()
    # No device plane (a CPU run), no trace, a program with no spans.
    assert read("device.idle_owned_share.loop", {}) is None
    assert read("device.idle_owned_share.loop",
                {"trace": {"/host:CPU": trace["/host:CPU"]}}) is None
    trace["/host:CPU"] = {"loop": [Event("bench.window", 0, 3e6)]}
    assert read("device.idle_owned_share.loop", {"trace": trace}) is None
    assert "[idle_owners]" not in capsys.readouterr().out


def test_idle_owners_line_names_the_ten_largest(capsys):
    read("device.idle_owned_share.loop", {"trace": hand_made_trace()})
    out = capsys.readouterr().out
    assert out.startswith(
        "[idle_owners] moolib.vtrace_learner.learn_stage=0.0015s "
        "moolib.acc.grad_to_host=0.0001s idle=0.0016s"
    )


def spans_manifest(tmp_path):
    """The rehearsal manifest with this PR's entries of the repo's
    manifest appended for ``tiny_atari_loop``, beside a link to the
    rehearsal's files: the rehearsal manifest itself is the benchmark's
    and stays as it is."""
    with open(REHEARSAL) as f:
        manifest = json.load(f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW_METRICS:
        manifest["per_layer"].append(
            dict(per_layer[name], workloads=["tiny_atari_loop"])
        )
    os.symlink(os.path.join(os.path.dirname(REHEARSAL), "benchmark"),
               tmp_path / "benchmark")
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def test_traced_loop_rehearsal_prints_the_ledger_metrics(tmp_path):
    proc = run_cell("tiny_atari_loop", trace=1, seconds=2,
                    manifest=spans_manifest(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    assert LEDGER_METRICS <= set(line["metrics"])
    for name in LEDGER_METRICS:
        assert line["metrics"][name]["value"] >= 0.0
    assert line["metrics"]["loop.unattributed_share"]["value"] < 10.0
    # The device's reader found no device plane on the CPU.
    assert "device.idle_owned_share.loop" not in line["metrics"]
    assert "[idle_owners]" not in proc.stdout


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    # Appended, in the issue's order, after what the benchmark had.
    assert [m["name"] for m in per_layer[-len(NEW_METRICS):]] == NEW_METRICS
    for m in per_layer[-len(NEW_METRICS):]:
        assert m["workloads"] == ["atari_loop"]
        assert m["moves"] == "loop_env_steps_per_s"
        assert os.path.exists(bench_run.reader_path(m["name"]))
