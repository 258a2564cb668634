"""The readers of what the loop's thread waits for in ``host_sync``: each
on hand-made readings, the cut of the phase's spans by what the chip ran on
a hand-made trace, a program older than its parts, and the loop's
rehearsal cell traced on the CPU, where the four metrics that read the
ledger's parts and the rows' columns are on the line and the two that read
the device are not."""

import json
import os
import sys

import pytest

from benchmark.lib import waits, xplane
from benchmark.lib.xplane import Event

from helpers import BENCH, REHEARSAL, REPO, last_line, run_cell

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402

LEDGER_METRICS = [
    "loop.sync_act_wait_ms_per_update", "loop.sync_readback_ms_per_update",
    "loop.env_step_ms_per_act", "loop.env_ready_idle_ms_per_act",
]
DEVICE_METRICS = [
    "loop.sync_behind_learn_ms_per_update",
    "loop.sync_chip_idle_ms_per_update",
]
NEW_METRICS = (LEDGER_METRICS[:2] + DEVICE_METRICS + LEDGER_METRICS[2:])

# Two readings of the loop's StepScope summary and the two rows beside
# them: four updates, 80 act calls of 128 env steps apart.
BEFORE = {"wall_s": 5.0, "phases": {"host_sync": 1.0, "other": 0.2}, "parts": {
    "host_sync.act_wait": 0.7, "host_sync.action_readback": 0.1,
    "host_sync.logits_readback": 0.15, "host_sync.unroll_write": 0.04,
}}
AFTER = {"wall_s": 15.0, "phases": {"host_sync": 3.0, "other": 0.5}, "parts": {
    "host_sync.act_wait": 2.3, "host_sync.action_readback": 0.18,
    "host_sync.logits_readback": 0.39, "host_sync.unroll_write": 0.1,
}}
ROWS = (
    {"updates": 6.0, "env_steps": 30_720, "env_step_s": 1.0,
     "env_ready_idle_s": 2.0},
    {"updates": 10.0, "env_steps": 40_960, "env_step_s": 1.24,
     "env_ready_idle_s": 2.4},
)
READINGS = {"stepscope": (BEFORE, AFTER), "rows": ROWS}
CONTEXT = {"cell": {"train_config": {"actor_batch_size": 128}}}
# A program older than its parts: the phases without them, the rows
# without the two columns.
OLD = {
    "stepscope": tuple(
        {k: v for k, v in s.items() if k != "parts"} for s in (BEFORE, AFTER)
    ),
    "rows": tuple(
        {k: v for k, v in r.items() if k in ("updates", "env_steps")}
        for r in ROWS
    ),
}


def read(name, readings, context=CONTEXT):
    return bench_run.load_reader(name)(dict(readings), context)


@pytest.mark.parametrize("name,expected", [
    # 1.6 s over 4 updates
    ("loop.sync_act_wait_ms_per_update", 400.0),
    # (0.08 + 0.24) s over 4 updates
    ("loop.sync_readback_ms_per_update", 80.0),
    # 0.24 s and 0.4 s over 80 act calls
    ("loop.env_step_ms_per_act", 3.0),
    ("loop.env_ready_idle_ms_per_act", 5.0),
])
def test_ledger_reader_on_hand_made_readings(name, expected):
    assert read(name, READINGS) == pytest.approx(expected)
    # Nothing to read: no readings, a window without an update or
    # without an act call.
    assert read(name, {}) is None
    still = dict(READINGS, rows=(ROWS[0], ROWS[0]))
    assert read(name, still) is None
    if name.endswith("_per_act"):
        assert read(name, READINGS, {}) is None  # no batch size to divide by


def test_sync_parts_line_shows_all_four_and_their_sum(capsys):
    read("loop.sync_readback_ms_per_update", READINGS)
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("[sync_parts]")]
    # ms an update / ms an act call; 1.98 s of parts in 2.0 s of phase.
    assert "act_wait=400.000/20.0000" in line
    assert "action_readback=20.000/1.0000" in line
    assert "logits_readback=60.000/3.0000" in line
    assert "unroll_write=15.000/0.7500" in line
    assert "parts=495.000 host_sync=500.000 parts_over_phase=0.9900" in line
    assert "updates=4 act_calls=80" in line
    # Without the cell's batch size: ms an update alone.
    read("loop.sync_readback_ms_per_update", READINGS, {})
    assert "act_wait=400.000 " in capsys.readouterr().out


def hand_made_trace(gradient="jit_step(123)", act="jit_act(456)"):
    """10 ms of window. The loop's thread is in `host_sync` twice: 1.0 to
    4.0 ms, over the last 1.5 ms of a gradient program (its operations
    leave 0.1 ms of it idle), a gap of 0.9 ms, 0.2 ms of the act program
    and 0.4 ms of nothing; and 6.0 to 6.5 ms, over 0.3 ms of a program
    that is neither and 0.2 ms of nothing. `act_wait` is the first 2.8 ms
    of the first and all of the second; one gradient step is dispatched."""
    us = 1e3
    loop = "moolib.vtrace_learner."
    return {
        "/device:TPU:0": {
            xplane.MODULES_LINE: [
                Event(gradient, 0, 2500 * us),
                Event(act, 3400 * us, 3600 * us),
                Event("jit__threefry_split(9)", 6100 * us, 6400 * us),
                Event(act, 11_000 * us, 11_200 * us),  # past the window
            ],
            xplane.OPS_LINE: [
                Event("fusion.1", 0, 2000 * us),
                Event("fusion.2", 2100 * us, 2500 * us),
                Event("fusion.3", 3400 * us, 3600 * us),
                Event("fusion.4", 6100 * us, 6400 * us),
            ],
        },
        "/host:CPU": {"loop": [
            Event("bench.window", 0, 10_000 * us),
            Event(loop + "step", 0, 10_000 * us),
            Event(loop + "grad_dispatch", 500 * us, 900 * us),
            Event(loop + "host_sync", 1000 * us, 4000 * us),
            Event(loop + "host_sync.act_wait", 1000 * us, 3800 * us),
            Event(loop + "host_sync.action_readback", 3800 * us, 3900 * us),
            Event(loop + "host_sync", 6000 * us, 6500 * us),
            Event(loop + "host_sync.act_wait", 6000 * us, 6500 * us),
        ]},
    }


def test_host_sync_spans_cut_by_what_the_chip_ran_to_the_nanosecond(capsys):
    found = waits.sync_device({"trace": hand_made_trace()})
    assert found["updates"] == 1
    assert found["host_sync"] == {
        "learn_program": 1_500_000.0, "act_program": 200_000.0,
        "other_program": 300_000.0, "no_program": 1_500_000.0,
        "ops_idle": 1_600_000.0, "spans": 3_500_000.0,
    }
    assert found["act_wait"] == {
        "learn_program": 1_500_000.0, "act_program": 200_000.0,
        "other_program": 300_000.0, "no_program": 1_300_000.0,
        "ops_idle": 1_400_000.0, "spans": 3_300_000.0,
    }
    for cut in (found["host_sync"], found["act_wait"]):
        assert sum(cut[c] for c in waits.CLASSES) == cut["spans"]
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("[sync_device]")]
    assert "host_sync:learn_program=1.500,act_program=0.200," in line
    assert "no_program=1.500,ops_idle=1.600,spans=3.500 act_wait:" in line
    # The two metrics, one pass: the line is printed once.
    readings = {"trace": hand_made_trace()}
    behind = bench_run.load_reader(DEVICE_METRICS[0])(readings, CONTEXT)
    idle = bench_run.load_reader(DEVICE_METRICS[1])(readings, CONTEXT)
    assert (behind, idle) == (pytest.approx(1.5), pytest.approx(1.6))
    assert capsys.readouterr().out.count("[sync_device]") == 1
    # Two gradient steps in the window: per update, half.
    trace = hand_made_trace()
    trace["/host:CPU"]["loop"].append(
        Event("moolib.vtrace_learner.grad_dispatch", 7e6, 7.2e6)
    )
    assert read(DEVICE_METRICS[0], {"trace": trace}) == pytest.approx(0.75)
    # A span that reaches past the window counts up to its edge.
    trace["/host:CPU"]["loop"][0] = Event("bench.window", 0, 3_000_000)
    found = waits.sync_device({"trace": trace})
    assert found["host_sync"]["spans"] == 2_000_000.0
    assert found["host_sync"]["learn_program"] == 1_500_000.0


@pytest.mark.parametrize("name", DEVICE_METRICS)
def test_device_reader_says_none_and_never_zero(name, capsys):
    # None of the learner's three programs on the modules line: nothing
    # says what ran, and the reason is printed.
    trace = hand_made_trace(gradient="jit_other(1)", act="jit_more(2)")
    assert read(name, {"trace": trace}) is None
    assert "[sync_device] no reading: none of" in capsys.readouterr().out
    # No gradient step dispatched in the window.
    trace = hand_made_trace()
    trace["/host:CPU"]["loop"] = [
        e for e in trace["/host:CPU"]["loop"]
        if not e.name.endswith("grad_dispatch")
    ]
    assert read(name, {"trace": trace}) is None
    assert "no grad_dispatch span" in capsys.readouterr().out
    # No trace, no device plane (a CPU run), a program without the parts:
    # nothing to read, and nothing to say.
    assert read(name, {}) is None
    trace = hand_made_trace()
    assert read(name, {"trace": {"/host:CPU": trace["/host:CPU"]}}) is None
    trace["/host:CPU"]["loop"] = [
        e for e in trace["/host:CPU"]["loop"] if ".host_sync." not in e.name
    ]
    assert read(name, {"trace": trace}) is None
    assert "[sync_device]" not in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_older_than_its_parts_gives_none(name, capsys):
    trace = hand_made_trace()
    trace["/host:CPU"]["loop"] = [
        e for e in trace["/host:CPU"]["loop"] if ".host_sync." not in e.name
    ]
    assert read(name, dict(OLD, trace=trace)) is None
    out = capsys.readouterr().out
    assert "[sync_parts]" not in out and "[sync_device]" not in out


def waits_manifest(tmp_path):
    """The rehearsal manifest with this PR's entries of the repo's
    manifest appended for ``tiny_atari_loop``, beside a link to the
    rehearsal's files (as ``test_spans.py`` does it: the rehearsal
    manifest itself is the benchmark's and stays as it is)."""
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


def test_traced_loop_rehearsal_prints_the_ledger_and_counter_metrics(
        tmp_path):
    proc = run_cell("tiny_atari_loop", trace=1, seconds=2,
                    manifest=waits_manifest(tmp_path))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc)
    assert line["correct"] is True
    assert set(LEDGER_METRICS) <= set(line["metrics"])
    for name in LEDGER_METRICS:
        assert line["metrics"][name]["value"] > 0.0
        assert line["metrics"][name]["unit"] == "ms"
    # The parts cover the phase: the reader's own line says how nearly.
    (parts,) = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("[sync_parts]")]
    over = float(parts.split("parts_over_phase=")[1].split()[0])
    assert 0.98 <= over <= 1.0, parts
    # The device's readers found no device plane on the CPU.
    for name in DEVICE_METRICS:
        assert name not in line["metrics"]
    assert "[sync_device]" not in proc.stdout


def test_every_new_metric_has_an_entry_and_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    sources = {"program_span": 2, "device_trace": 2, "program_counter": 2}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert m["workloads"] == ["atari_loop"]
        assert m["moves"] == "loop_env_steps_per_s"
        assert (m["unit"], m["better"]) == ("ms", "lower")
        sources[m["source"]] -= 1
        assert os.path.exists(os.path.join(
            BENCH, "metrics", name + ".py"
        ))
    assert set(sources.values()) == {0}
