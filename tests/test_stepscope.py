"""stepscope: step-phase attribution + critical-path fractions (ISSUE 20).

Unit layer drives the context managers on a fake monotonic clock so the
self-time ledger arithmetic is pinned exactly (nesting, residual
``other``, overrun, windowed gauges). The acceptance layer runs the real
seeded A2C cohort (in-process broker + accumulator peer + EnvPool
workers) and asserts the ISSUE 20 criteria: ledgers sum to wall within
5%, the three derived fractions appear in a live ``__telemetry`` scrape
AND a flightrec bundle AND schema-valid trend rows, and a deliberately
serialized (``overlap_comms=False``) run shows strictly higher
exposed-comms than the overlapped baseline.
"""

import collections
import dataclasses
import json
import threading
import time

import pytest

from moolib_tpu.bench.harness import stepscope_trend_rows
from moolib_tpu.telemetry import (
    StepScope,
    Telemetry,
    summarize_stepscope,
)
from moolib_tpu.telemetry.stepscope import (
    FRACTION_GAUGES,
    PHASE_CLASS,
    merge_summaries,
    phase_trace,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    clk = FakeClock()
    monkeypatch.setattr(time, "monotonic", clk)
    return clk


def _scope(**kw):
    return StepScope(kw.pop("loop", "loop"),
                     telemetry=kw.pop("telemetry", None) or Telemetry("t"),
                     **kw)


# -- ledger arithmetic --------------------------------------------------------


def test_nested_phases_self_time_and_other_residual(clock):
    scope = _scope()
    with scope.step():
        with scope.phase("grad_allreduce"):
            clock.advance(0.3)
            with scope.phase("host_sync"):
                clock.advance(0.5)
            clock.advance(0.2)
        clock.advance(1.0)  # unattributed -> "other"
    s = scope.summary()
    # Self-time: the nested host_sync's 0.5s is attributed to host_sync
    # ONLY; the enclosing comms phase keeps its own 0.5s.
    assert s["phases"] == pytest.approx(
        {"grad_allreduce": 0.5, "host_sync": 0.5, "other": 1.0})
    assert s["wall_s"] == pytest.approx(2.0)
    assert s["fractions"]["exposed_comms"] == pytest.approx(0.25)
    assert s["fractions"]["host_blocked"] == pytest.approx(0.25)
    assert s["fractions"]["env_wait"] == 0.0
    # Ledger closes exactly: explicit + other == wall.
    assert sum(s["phases"].values()) == pytest.approx(s["wall_s"])


def test_repeated_phase_accumulates_and_gauges_track_window(clock):
    scope = _scope(window=2)
    reg = scope._tel.registry
    for comms in (0.8, 0.2, 0.4):
        with scope.step():
            with scope.phase("wire_wait"):
                clock.advance(comms)
            with scope.phase("wire_wait"):
                clock.advance(0.0)
            clock.advance(1.0 - comms)
    # Windowed gauge: only the LAST 2 steps (0.2 + 0.4 over 2.0s walls).
    g = reg.snapshot()[f'{FRACTION_GAUGES["comms"]}{{loop="loop"}}']
    assert g["value"] == pytest.approx(0.3)
    # Cumulative counters carry the lifetime total.
    assert scope.summary()["phases"]["wire_wait"] == pytest.approx(1.4)
    assert scope.summary()["fractions"]["exposed_comms"] == pytest.approx(
        1.4 / 3.0)


def test_note_overrun_surfaces_as_gauge_not_corrupt_fractions(clock):
    scope = _scope()
    with scope.step():
        clock.advance(1.0)
        # Externally timed addition that overlaps the same wall second:
        # explicit 1.5s > wall 1.0s. The overrun is surfaced, never
        # silently rescaled into the fractions.
        scope.note("host_sync", 1.5)
    snap = scope._tel.snapshot()
    assert snap['stepscope_ledger_overrun_fraction{loop="loop"}'][
        "value"] == pytest.approx(0.5)
    assert snap['stepscope_attributed_fraction{loop="loop"}'][
        "value"] == pytest.approx(1.0)
    s = scope.summary()
    assert "other" not in s["phases"]
    assert s["fractions"]["host_blocked"] == pytest.approx(1.5)


def test_observe_step_threadsafe_aggregation(clock):
    scope = _scope()
    n, per = 8, 50

    def worker():
        for _ in range(per):
            scope.observe_step(0.01, {"env_wait": 0.004, "staging": 0.002})

    threads = [threading.Thread(target=worker) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = scope.summary()
    assert s["steps"] == n * per
    assert s["wall_s"] == pytest.approx(n * per * 0.01)
    assert s["phases"]["env_wait"] == pytest.approx(n * per * 0.004)
    assert s["fractions"]["env_wait"] == pytest.approx(0.4)
    assert s["fractions"]["host_blocked"] == pytest.approx(0.2)


def test_gate_off_records_nothing_and_mid_step_flip_is_safe(clock):
    tel = Telemetry("t", enabled=False)
    scope = _scope(telemetry=tel)
    with scope.step():
        with scope.phase("env_wait"):
            clock.advance(1.0)
    scope.observe_step(1.0, {"env_wait": 1.0})
    assert scope.summary()["steps"] == 0
    # Gate snapshot at step entry: enabling mid-step must not produce a
    # torn ledger (the step stays off); the NEXT step records.
    with scope.step():
        tel.set_enabled(True)
        with scope.phase("env_wait"):
            clock.advance(1.0)
    assert scope.summary()["steps"] == 0
    with scope.step():
        with scope.phase("env_wait"):
            clock.advance(1.0)
    assert scope.summary()["steps"] == 1
    # ... and disabling mid-step closes the in-flight step cleanly.
    with scope.step():
        tel.set_enabled(False)
        with scope.phase("env_wait"):
            clock.advance(1.0)
    assert scope.summary()["steps"] == 2


def test_close_unregisters_gauges_keeps_cumulative_series(clock):
    scope = _scope()
    with scope.step():
        with scope.phase("env_wait"):
            clock.advance(0.5)
    scope.close()
    snap = scope._tel.snapshot()
    assert not any("fraction{" in sid and "phase_fraction" not in sid
                   for sid in snap), sorted(snap)
    # Counters survive their producer, like every other registry series.
    assert snap['stepscope_steps_total{loop="loop"}']["value"] == 1
    assert 'stepscope_phase_seconds_total{loop="loop",phase="env_wait"}' \
        in snap


def test_flight_events_and_trace_spans(clock, monkeypatch):
    # The TraceBuffer is on the wall clock: tie it to the fake one so
    # placement is exact.
    monkeypatch.setattr(time, "time", lambda: 1_000.0 + clock.t)
    tel = Telemetry("t", tracing=True)
    scope = _scope(telemetry=tel, flight_every=2)
    for i in range(4):
        scope.observe_step(1.0, {"grad_allreduce": 0.25})
    events = [e for e in tel.flight.events() if e["kind"] == "step_phases"]
    assert [e["fields"]["steps"] for e in events] == [2, 4]
    assert events[-1]["fields"]["loop"] == "loop"
    assert events[-1]["fields"]["exposed_comms"] == pytest.approx(0.25)
    assert events[-1]["fields"]["wall_s"] == pytest.approx(4.0)
    # observe_step producers keep their counters and draw no span: their
    # steps overlap or end on another thread.
    assert not [s for s in tel.traces.spans() if s.cat == "stepscope"]

    # A step on the loop's own thread: every phase's span is where the
    # phase was, not drawn back to back from the step's start.
    mine = _scope(telemetry=tel, loop="mine")
    with mine.step():
        clock.advance(0.5)  # `other`: no placement, so no span
        with mine.phase("outer"):
            clock.advance(0.25)
            with mine.phase("inner"):
                clock.advance(0.125)
            clock.advance(0.125)
        clock.advance(1.0)
        with mine.phase("inner"):
            clock.advance(0.25)
    spans = [s for s in tel.traces.spans() if s.cat == "stepscope"]
    assert sorted(s.name for s in spans) == [
        "moolib.mine.inner", "moolib.mine.inner", "moolib.mine.outer",
        "moolib.mine.step",
    ]
    assert all(s.args == {"loop": "mine"} and s.pid == "t" for s in spans)
    step = next(s for s in spans if s.name == "moolib.mine.step")
    outer = next(s for s in spans if s.name == "moolib.mine.outer")
    inner, again = sorted(
        (s for s in spans if s.name == "moolib.mine.inner"),
        key=lambda s: s.ts,
    )
    assert step.dur == 2_250_000
    assert (outer.ts - step.ts, outer.dur) == (500_000, 500_000)
    # Nested phases nest: the child lies inside its parent.
    assert (inner.ts - step.ts, inner.dur) == (750_000, 125_000)
    assert outer.ts <= inner.ts and inner.ts + inner.dur <= outer.ts + outer.dur
    assert (again.ts - step.ts, again.dur) == (2_000_000, 250_000)
    # ... and last what the ledger says: a leaf phase's spans add up to
    # its seconds, a parent's span less its children's to its self time.
    phases = mine.summary()["phases"]
    assert (inner.dur + again.dur) / 1e6 == pytest.approx(phases["inner"])
    assert (outer.dur - inner.dur) / 1e6 == pytest.approx(phases["outer"])
    assert phases["other"] == pytest.approx(1.5)
    names = {e["name"] for e in tel.chrome_trace()["traceEvents"]
             if e.get("cat") == "stepscope"}
    assert names == {"moolib.mine.step", "moolib.mine.outer",
                     "moolib.mine.inner"}


def test_profiler_capture_holds_the_step_and_its_phases(tmp_path):
    """The other sink: during a live jax profiler session the same
    ``with`` lands on the xplane's host plane, on the profiler's clock,
    with the phases inside the step on one line."""
    import jax
    from jax.profiler import ProfileData

    scope = _scope(loop="prof")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with scope.step():
            with scope.phase("first"):
                time.sleep(0.002)
            with scope.phase("second"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    lines = [
        {e.name: (e.start_ns, e.start_ns + e.duration_ns)
         for e in line.events if e.name.startswith("moolib.prof.")}
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
    ]
    (line,) = [found for found in lines if found]
    assert set(line) == {"moolib.prof.step", "moolib.prof.first",
                         "moolib.prof.second"}
    step, first, second = (line["moolib.prof." + n]
                           for n in ("step", "first", "second"))
    assert step[0] <= first[0] < first[1] <= second[0] < second[1] <= step[1]
    assert first[1] - first[0] >= 2e6 and second[1] - second[0] >= 2e6


def _turn_with_host_sync(scope, clock, parts: bool):
    """One turn with a `host_sync` of 1.0 s (0.5 + 0.25 + 0.125 + 0.125),
    0.25 s of `env_wait` and 0.5 s nobody claims; `parts` says whether the
    phase is divided."""
    def part(name):
        return scope.part(name) if parts else scope.phase("host_sync")

    with scope.step():
        with scope.phase("env_wait"):
            clock.advance(0.25)
        with scope.phase("host_sync"):
            for name, dt in (("act_wait", 0.5), ("action_readback", 0.25),
                             ("logits_readback", 0.125),
                             ("unroll_write", 0.125)):
                if parts:
                    with scope.part(name):
                        clock.advance(dt)
                else:
                    clock.advance(dt)
        clock.advance(0.5)


def test_parts_divide_a_phase_and_change_nothing_it_reads(clock, monkeypatch):
    monkeypatch.setattr(time, "time", lambda: 1_000.0 + clock.t)
    plain_tel, tel = Telemetry("plain"), Telemetry("t", tracing=True)
    plain, scope = _scope(telemetry=plain_tel), _scope(telemetry=tel)
    for _ in range(3):
        _turn_with_host_sync(plain, clock, parts=False)
        _turn_with_host_sync(scope, clock, parts=True)
    want, got = plain.summary(), scope.summary()
    # The phase, `other`, the class fractions and the closure read as
    # they do in a scope that never heard of parts.
    assert want.pop("parts") == {}
    parts = got.pop("parts")
    assert got == want
    assert got["phases"] == {"env_wait": pytest.approx(0.75),
                             "host_sync": pytest.approx(3.0),
                             "other": pytest.approx(1.5)}
    assert sum(got["phases"].values()) == pytest.approx(got["wall_s"])
    assert got["fractions"]["host_blocked"] == pytest.approx(3.0 / 5.25)
    divided = tel.snapshot()
    for sid, series in plain_tel.snapshot().items():
        if sid.startswith("stepscope_") and "value" in series:
            assert divided[sid]["value"] == pytest.approx(series["value"])
    # The parts, under the summary's own key, cover the phase.
    assert parts == {
        "host_sync.act_wait": pytest.approx(1.5),
        "host_sync.action_readback": pytest.approx(0.75),
        "host_sync.logits_readback": pytest.approx(0.375),
        "host_sync.unroll_write": pytest.approx(0.375),
    }
    assert sum(parts.values()) == pytest.approx(got["phases"]["host_sync"])
    # ... as a counter labelled by loop, phase and part, from which a
    # frozen snapshot gives the live summary back.
    snap = tel.snapshot()
    series = [sid for sid in snap
              if sid.startswith("stepscope_part_seconds_total")]
    assert len(series) == 4
    for sid in series:
        assert 'loop="loop"' in sid and 'phase="host_sync"' in sid
    recon = summarize_stepscope(snap)["loop"]
    recon.pop("window")
    assert recon == scope.summary()
    assert not [sid for sid in plain_tel.snapshot()
                if sid.startswith("stepscope_part_seconds_total")]
    _turn_with_host_sync(plain, clock, parts=False)  # a peer of its own
    merged = merge_summaries({"a": {"loop": scope.summary()},
                              "b": {"loop": plain.summary()}})["loop"]
    assert merged["parts"] == parts
    assert merged["phases"]["host_sync"] == pytest.approx(7.0)
    # ... and as spans from the same clock readings, each inside the
    # phase's span, the phase inside the turn's.
    spans = [s for s in tel.traces.spans() if s.cat == "stepscope"]
    names = sorted({s.name for s in spans})
    assert names == [
        "moolib.loop.env_wait", "moolib.loop.host_sync",
        "moolib.loop.host_sync.act_wait",
        "moolib.loop.host_sync.action_readback",
        "moolib.loop.host_sync.logits_readback",
        "moolib.loop.host_sync.unroll_write", "moolib.loop.step",
    ]
    first = {}
    for s in sorted(spans, key=lambda s: s.ts):
        first.setdefault(s.name, s)
    step, phase = first["moolib.loop.step"], first["moolib.loop.host_sync"]
    assert step.ts <= phase.ts and phase.ts + phase.dur <= step.ts + step.dur
    at = phase.ts
    for name, dur in (("act_wait", 500_000), ("action_readback", 250_000),
                      ("logits_readback", 125_000),
                      ("unroll_write", 125_000)):
        part = first["moolib.loop.host_sync." + name]
        assert (part.ts, part.dur) == (at, dur)
        at += dur
    assert at == phase.ts + phase.dur


def test_part_outside_a_phase_or_with_telemetry_off_is_a_noop(clock):
    tel = Telemetry("t", tracing=True)
    scope = _scope(telemetry=tel)
    with scope.part("stray"):  # no step at all
        clock.advance(1.0)
    with scope.step():
        with scope.part("stray"):  # a step, but no phase to divide
            clock.advance(1.0)
        with scope.phase("p"):
            clock.advance(1.0)
    assert scope.summary()["parts"] == {}
    assert scope.summary()["phases"] == {"p": pytest.approx(1.0),
                                         "other": pytest.approx(1.0)}
    assert "moolib.loop.p.stray" not in {s.name for s in tel.traces.spans()}
    # Telemetry off when the step was entered: the flip inside it turns
    # nothing on, for the phase or for its part.
    tel.set_enabled(False)
    with scope.step():
        tel.set_enabled(True)
        with scope.phase("p"):
            with scope.part("late"):
                clock.advance(1.0)
    assert scope.summary()["steps"] == 1
    assert scope.summary()["parts"] == {}
    assert not [sid for sid in tel.snapshot()
                if sid.startswith("stepscope_part_seconds_total")]
    # On at entry: the part is there, nested in whichever phase is open.
    with scope.step():
        with scope.phase("p"):
            with scope.phase("q"):
                with scope.part("late"):
                    clock.advance(0.5)
    assert scope.summary()["parts"] == {"q.late": pytest.approx(0.5)}
    assert scope.summary()["phases"]["q"] == pytest.approx(0.5)


def test_gate_off_enters_no_annotation_and_records_no_span(monkeypatch):
    entered = []

    class Annotation:
        @staticmethod
        def is_enabled():
            return True  # as if a profiler session were live

        def __init__(self, name):
            entered.append(name)

        def __exit__(self, *exc):
            pass

    def run(tel):
        scope = _scope(telemetry=tel)
        spans = [scope.step()._span, scope.phase("a")._span,
                 tel.span("moolib.acc.block")]
        for span in spans:
            monkeypatch.setattr(span, "_annotate", Annotation)
        with scope.step():
            with scope.phase("a"):
                pass
        with spans[-1]:
            pass

    off = Telemetry("off", enabled=False, tracing=True)
    run(off)
    assert entered == [] and len(off.traces) == 0
    on = Telemetry("on", tracing=True)
    run(on)
    assert entered == ["moolib.loop.step", "moolib.loop.a", "moolib.acc.block"]
    assert [s.name for s in on.traces.spans()] == [
        "moolib.loop.a", "moolib.loop.step", "moolib.acc.block",
    ]


def test_phase_cost_ceiling_with_no_session_live():
    """A phase with telemetry on and no profiler session: the ledger
    entry, one static check for a session, nothing else. The ceiling is
    generous (a phase measures about a microsecond) and the statistic a
    median, so a loaded runner cannot flap it."""
    import statistics

    from jax.profiler import TraceAnnotation

    assert not TraceAnnotation.is_enabled()
    scope = _scope()
    cm = scope.phase("p")
    samples = []
    with scope.step():
        for _ in range(10_000):
            t0 = time.perf_counter()
            with cm:
                pass
            samples.append(time.perf_counter() - t0)
    assert statistics.median(samples) < 50e-6
    assert scope.summary()["phases"]["p"] > 0.0


# -- snapshot analysis --------------------------------------------------------


def test_summarize_metrics_matches_live_summary(clock):
    tel = Telemetry("t")
    scope = _scope(telemetry=tel)
    for _ in range(3):
        scope.observe_step(2.0, {"wire_wait": 0.5, "host_sync": 0.25,
                                 "queue_wait": 0.25})
    live = scope.summary()
    recon = summarize_stepscope(tel.snapshot())["loop"]
    window = recon.pop("window")
    assert recon == live
    assert window["comms"] == pytest.approx(0.25)
    assert window["attributed"] == pytest.approx(0.5)
    assert window["ledger_overrun"] == 0.0
    # After close() the gauges are gone; the cumulative reconstruction
    # still works (the dead-peer bundle story).
    scope.close()
    assert summarize_stepscope(tel.snapshot())["loop"] == live


def test_merge_summaries_dedups_shared_global_registry(clock):
    tel = Telemetry("t")
    scope = _scope(telemetry=tel)
    scope.observe_step(1.0, {"env_wait": 0.5})
    one = summarize_stepscope(tel.snapshot())
    # Two peers in one OS process scrape the same global registry: the
    # cohort merge must count the shared loop once, not twice.
    merged = merge_summaries({"peer-a": one, "peer-b": one})
    assert merged["loop"]["steps"] == 1
    assert merged["loop"]["fractions"]["env_wait"] == pytest.approx(0.5)
    # Genuinely distinct summaries sum.
    scope.observe_step(1.0, {"env_wait": 0.5})
    two = summarize_stepscope(tel.snapshot())
    merged = merge_summaries({"peer-a": one, "peer-b": two})
    assert merged["loop"]["steps"] == 3


def test_phase_trace_composition_tracks(clock):
    tel = Telemetry("t")
    scope = _scope(telemetry=tel)
    scope.observe_step(1.0, {"env_wait": 0.75, "staging": 0.25})
    trace = phase_trace({"p": summarize_stepscope(tel.snapshot())},
                        pid_base=7)
    bars = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert {e["name"] for e in bars} == {"phase env_wait", "phase staging"}
    assert all(e["pid"] == 8 for e in bars)
    # Widths proportional to cumulative seconds, drawn back-to-back.
    by_name = {e["name"]: e for e in bars}
    assert by_name["phase env_wait"]["dur"] == 750_000
    assert by_name["phase staging"]["ts"] == 750_000
    json.dumps(trace)  # plain JSON, Perfetto-loadable


def test_malicious_phase_names_bounded_by_cardinality_guard(clock):
    from moolib_tpu.telemetry.registry import Registry

    tel = Telemetry("t")
    tel.registry = Registry(label_cardinality=8)
    scope = _scope(telemetry=tel)
    for i in range(50):
        scope.observe_step(0.01, {f"phase{i}": 0.01})
    phase_series = [sid for sid in tel.snapshot()
                    if sid.startswith("stepscope_phase_seconds_total")]
    # 8 admitted values + the overflow fold — never 50 series.
    assert len(phase_series) <= 9
    assert any('phase="other"' in sid for sid in phase_series)


# -- acceptance: the seeded A2C cohort ----------------------------------------


def _a2c_cfg(**overrides):
    from moolib_tpu.examples.a2c import A2CConfig

    base = dict(seed=0, total_steps=1200, log_interval_steps=600,
                num_processes=2, batch_size=2, num_batches=2)
    base.update(overrides)
    return A2CConfig(**base)


def _global_stepscope_summaries():
    from moolib_tpu.telemetry import global_telemetry

    return summarize_stepscope(global_telemetry().snapshot())


def _exposed_comms_totals():
    """(grad_allreduce+wire_wait seconds, wall seconds) for a2c_learner
    from the process-global registry — cumulative, so acceptance runs
    diff them (the registry outlives each train() call)."""
    s = _global_stepscope_summaries().get("a2c_learner")
    if s is None:
        return 0.0, 0.0
    comms = sum(secs for ph, secs in s["phases"].items()
                if PHASE_CLASS.get(ph) == "comms")
    return comms, s["wall_s"]


@pytest.mark.integration
def test_acceptance_a2c_cohort_fractions_everywhere():
    """ISSUE 20 acceptance on the real cohort: ledgers close within 5%,
    fractions in a live ``__telemetry`` scrape, in a flightrec bundle,
    and as schema-valid trend rows."""
    from moolib_tpu.bench.harness import parse_result
    from moolib_tpu.examples.a2c import train
    from moolib_tpu.flightrec.bundle import snapshot_bundle, validate_bundle
    from moolib_tpu.rpc import Rpc
    from moolib_tpu.telemetry import global_telemetry

    comms0, wall0 = _exposed_comms_totals()
    steps0 = _global_stepscope_summaries().get(
        "a2c_learner", {}).get("steps", 0)

    done = threading.Event()
    logs = []

    def run():
        try:
            logs.extend(train(_a2c_cfg(), log_fn=lambda s: None))
        finally:
            done.set()

    trainer = threading.Thread(target=run, daemon=True)
    trainer.start()
    # LIVE scrape while the loops run: any Rpc's __telemetry merges the
    # process-global registry, so the windowed fraction gauges must be
    # visible over the wire mid-training.
    server = Rpc("stepscope-live")
    client = Rpc("stepscope-probe",
                 telemetry=Telemetry("probe", enabled=False))
    server.listen("127.0.0.1:0")
    client.connect(server.debug_info()["listen"][0])
    client.set_timeout(10.0)
    live_gauges = {}
    try:
        deadline = time.monotonic() + 90.0
        want = {f'{name}{{loop="a2c_learner"}}'
                for name in FRACTION_GAUGES.values()}
        while time.monotonic() < deadline and not done.is_set():
            metrics = client.sync("stepscope-live", "__telemetry")["metrics"]
            found = {sid: metrics[sid]["value"]
                     for sid in want if sid in metrics}
            if len(found) == len(want):
                live_gauges = found
                break
            time.sleep(0.25)
    finally:
        client.close()
        server.close()
        trainer.join(timeout=180)
    assert done.is_set(), "training did not finish"
    assert logs, "training produced no logs"
    assert set(live_gauges) == want, (
        f"fractions missing from live scrape: got {sorted(live_gauges)}"
    )
    assert all(0.0 <= v <= 1.0 for v in live_gauges.values()), live_gauges

    summaries = _global_stepscope_summaries()
    learner = summaries["a2c_learner"]
    assert learner["steps"] - steps0 > 0
    # Ledger closure within 5% (cumulative: explicit + other vs wall).
    for loop, s in summaries.items():
        if s["steps"] == 0:
            continue
        err = abs(sum(s["phases"].values()) - s["wall_s"]) / s["wall_s"]
        assert err <= 0.05, f"{loop}: ledger closure {err:.1%}"
    # Envpool attribution rode along from the worker tier. CartPole
    # steps in microseconds, so most of a batch's wall is the finished
    # batch waiting for the learner, which is no starvation.
    envpool = summaries["envpool"]
    assert 0.0 < envpool["fractions"]["env_wait"] < 0.5
    assert envpool["phases"]["ready_idle"] > envpool["phases"]["batch_fill"]

    # Flightrec: the frozen bundle carries both the step_phases stamps
    # and enough metrics to reconstruct the fractions after death.
    bundle = validate_bundle(snapshot_bundle(
        global_telemetry(), trigger="test", detail="stepscope acceptance"))
    stamps = [e for e in bundle["events"] if e["kind"] == "step_phases"]
    assert stamps, "no step_phases events in the bundle"
    assert {e["fields"]["loop"] for e in stamps} >= {"a2c_learner"}
    for e in stamps:
        assert 0.0 <= e["fields"]["exposed_comms"] <= 1.0
    recon = {}
    for _src, snap in bundle["metrics"].items():
        recon.update(summarize_stepscope(snap))
    assert recon["a2c_learner"]["fractions"]["exposed_comms"] == \
        pytest.approx(learner["fractions"]["exposed_comms"])

    # Trend rows: schema-valid through the strict parser, loop-qualified.
    rows = stepscope_trend_rows(
        learner, smoke=True, cmd="python tools/stepscope_report.py --smoke")
    for row in rows:
        assert parse_result(dataclasses.asdict(row)) == row
    assert {r.metric for r in rows} == {
        "stepscope_a2c_learner_exposed_comms_fraction",
        "stepscope_a2c_learner_host_blocked_fraction",
        "stepscope_a2c_learner_env_wait_fraction",
    }


@pytest.mark.integration
def test_acceptance_serialized_comms_strictly_higher_than_overlap():
    """``overlap_comms=False`` puts the gradient reduction on the
    critical path; exposed_comms_fraction is exactly the gauge that
    tells the two modes apart — the serialized run must read strictly
    higher. Computed as per-run deltas of the cumulative counters (the
    process-global registry accretes across train() calls)."""
    from moolib_tpu.examples.a2c import train

    comms0, wall0 = _exposed_comms_totals()
    train(_a2c_cfg(), log_fn=lambda s: None)
    comms1, wall1 = _exposed_comms_totals()
    train(_a2c_cfg(overlap_comms=False), log_fn=lambda s: None)
    comms2, wall2 = _exposed_comms_totals()

    overlap_frac = (comms1 - comms0) / (wall1 - wall0)
    serial_frac = (comms2 - comms1) / (wall2 - wall1)
    assert wall1 > wall0 and wall2 > wall1
    assert serial_frac > overlap_frac, (
        f"serialized exposed_comms {serial_frac:.4f} not above "
        f"overlapped baseline {overlap_frac:.4f}"
    )


#: Every phase of one turn of the vtrace loop (PERF.md section 3 says
#: which metric reads which).
VTRACE_TURN_PHASES = (
    "env_wait", "unroll_cat", "obs_stage", "act_dispatch", "host_sync",
    "env_submit", "acc_update", "learn_batch_get", "learn_stage",
    "grad_dispatch", "grad_allreduce", "grad_result", "grad_stage",
    "apply_dispatch", "metrics_drain", "log", "checkpoint",
)


@pytest.mark.integration
def test_vtrace_train_names_every_part_of_its_turn(tmp_path):
    from moolib_tpu.examples.vtrace.experiment import VtraceConfig, train
    from moolib_tpu.telemetry import global_telemetry

    def reading():
        return summarize_stepscope(global_telemetry().snapshot()).get(
            "vtrace_learner", {"wall_s": 0.0, "phases": {}}
        )

    before = reading()
    logs = train(
        VtraceConfig(
            env="synthetic", num_actions=4, episode_length=40,
            total_steps=1_280, actor_batch_size=8, learn_batch_size=8,
            virtual_batch_size=8, num_actor_processes=2,
            num_actor_batches=2, unroll_length=4, log_interval_steps=320,
            stats_interval=1e9, savedir=str(tmp_path),
            checkpoint_interval=0.0, checkpoint_history_interval=None,
            seed=0,
        ),
        log_fn=lambda *a, **k: None,
    )
    assert logs and logs[-1]["updates"] >= 1
    after = reading()
    spent = {
        name: secs - before["phases"].get(name, 0.0)
        for name, secs in after["phases"].items()
    }
    missing = [p for p in VTRACE_TURN_PHASES if spent.get(p, 0.0) <= 0.0]
    assert not missing, missing
    assert not {"act", "fwd_bwd", "optimizer"} & set(spent)
    wall = after["wall_s"] - before["wall_s"]
    assert spent["other"] <= 0.10 * wall, (spent, wall)
    # The host-side names count toward the host-blocked fraction.
    for name in ("unroll_cat", "obs_stage", "learn_batch_get",
                 "learn_stage", "grad_stage", "metrics_drain"):
        assert PHASE_CLASS[name] == "host"
    for name in ("act_dispatch", "grad_dispatch", "apply_dispatch"):
        assert name not in PHASE_CLASS


@pytest.mark.integration
def test_vtrace_train_divides_host_sync_and_times_the_envs(tmp_path):
    """`host_sync`'s four parts cover it, and the rows carry the envs' own
    step and the time their batches lay ready, both rising."""
    from moolib_tpu.examples.vtrace.experiment import VtraceConfig, train
    from moolib_tpu.telemetry import global_telemetry

    def reading():
        return summarize_stepscope(global_telemetry().snapshot()).get(
            "vtrace_learner", {"phases": {}, "parts": {}}
        )

    before = reading()
    tel = global_telemetry()
    was_tracing, t0_us = tel.tracing, int(time.time() * 1e6)
    tel.set_tracing(True)  # the spans too, to count the entries
    try:
        logs = train(
            VtraceConfig(
                env="synthetic", num_actions=4, episode_length=40,
                total_steps=1_920, actor_batch_size=16, learn_batch_size=16,
                virtual_batch_size=16, num_actor_processes=2,
                num_actor_batches=2, unroll_length=4, log_interval_steps=640,
                stats_interval=1e9, seed=0,
            ),
            log_fn=lambda *a, **k: None,
        )
    finally:
        tel.set_tracing(was_tracing)
    after = reading()
    parts = {
        key: secs - before["parts"].get(key, 0.0)
        for key, secs in after["parts"].items()
    }
    assert set(parts) == {
        "host_sync.act_wait", "host_sync.action_readback",
        "host_sync.logits_readback", "host_sync.unroll_write",
    }
    assert all(secs > 0.0 for secs in parts.values()), parts
    host_sync = (after["phases"]["host_sync"]
                 - before["phases"].get("host_sync", 0.0))
    assert sum(parts.values()) <= host_sync
    assert sum(parts.values()) >= 0.98 * host_sync, (parts, host_sync)
    # Every act call that is finished enters the phase once and each of
    # its four parts once, in it. (Which part is the long one is the
    # turn's doing: with another batch's call dispatched meanwhile, the
    # wait for the device may well be the shortest.)
    entered = collections.Counter(
        s.name.rpartition("vtrace_learner.")[2]
        for s in tel.traces.spans()
        if s.cat == "stepscope" and s.ts >= t0_us
        and s.name.startswith("moolib.vtrace_learner.host_sync")
    )
    assert entered["host_sync"] >= 1_920 // 16
    assert entered == dict.fromkeys(
        ["host_sync", *parts], entered["host_sync"]
    ), entered
    assert len(logs) >= 2
    for name in ("env_step_s", "env_ready_idle_s"):
        column = [row[name] for row in logs]
        assert column[0] > 0.0, (name, column)
        assert all(b > a for a, b in zip(column, column[1:])), (name, column)
