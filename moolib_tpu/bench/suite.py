"""The CPU-proxy perf suite: host-plane hot-path benchmarks that run on
every PR and need no accelerator.

These are not device numbers (``benchmark/run.py`` measures those, on the
chip): they measure the host-side hot paths the device loop sits on top
of:

==============================  ============================================
benchmark                       hot path it guards
==============================  ============================================
``rpc_echo_latency_s``          RPC dispatch floor (serialize, loop hop,
                                wire, dispatch, respond) — every control
                                message pays it
``rpc_payload_gbps``            large-payload RPC throughput over loopback
                                TCP — gradient and rollout transfers
``rpc_shm_payload_gbps``        the same payload echo over the same-host
                                shm ring lane (spill-slot writes, zero-copy
                                receive) — the PR-14 acceptance row
                                (docs/perf.md records the >=3x-over-TCP
                                evidence); the bench errors if payloads
                                fell back to TCP, and the trend detector
                                gates against recorded history
``allreduce_tree_gbps``         loopback DCN tree allreduce — the
                                Accumulator's cross-host reduce plane
``batcher_fill_s``              two-stage batching fill latency — the
                                acting-plane staging path
``envpool_steps_per_s``         trivial-env EnvPool dispatch ceiling — shm
                                slab writes, ring dispatch, worker loop
                                (plus the supervision-overhead A/B in
                                ``extra``, budget-gated < 5%)
``envpool_recovery_s``          env-tier failover budget: SIGKILL one
                                worker -> first post-respawn step
``serial_encode_gbps`` /        wire serialization of tensor payloads —
``serial_decode_gbps``          under every RPC byte
``statestore_replicate_gbps``   durable-state publish pipeline (encode,
                                chunk + sha256, crash-atomic local write,
                                offer/ingest/commit push to one loopback
                                replica) — the rate at which a committed
                                model version becomes peer-durable
``serving_qps`` /               serving-tier closed loop (router dispatch,
``serving_p99_latency_s``       admission, dynamic batching in jit) —
                                throughput and the tail the robustness
                                layer keeps bounded
``fleet_rollout_s``             fleet-tier control-plane latency: one
                                zero-downtime canary rollout (canary
                                publish, weighted settle, promote) through
                                a spec-materialized cohort under
                                closed-loop load — floored by the fixed
                                settle window, so the row watches the
                                machinery around it
==============================  ============================================

Every benchmark follows the harness protocol (warmup + repeats +
trimmed stats, ``time.perf_counter`` only), listens on OS-assigned ports,
attaches a telemetry-registry snapshot (so the run doubles as a scrape
fixture and the budget layer can read p50/p99 straight off the exported
histograms), and stamps a reproduce command. ``smoke=True`` shrinks sizes
and repeats to fit the CI wall-clock cap; full mode is for trend-quality
local runs.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .harness import BenchResult, clock, measure, trimmed_stats

__all__ = ["CPU_PROXY_SUITE", "TrivialEnv", "run_suite"]

SUITE_NAME = "cpu-proxy"


def _cmd(name: str, smoke: bool) -> str:
    return (
        f"python tools/perf.py --suite {SUITE_NAME} --only {name}"
        + (" --smoke" if smoke else "")
    )


#: Per-benchmark trend-tolerance overrides: the OBSERVED run-to-run
#: variance of each proxy on the shared 1-core CI container (e.g. serial
#: encode swung 46% between back-to-back clean runs — ms-scale CPU-bound
#: loops are at the mercy of noisy neighbours). These bands make the
#: trend gate a structural-slowdown detector (an accidental copy, a sync
#: in a hot loop — 2x-class steps) rather than a flake source; the
#: absolute budget floors/ceilings still guard catastrophes, and quiet
#: hosts can tighten with ``perf.py --tolerance``-driven re-checks.
TREND_TOLERANCE = {
    "rpc_echo_latency_s": 0.5,
    "rpc_payload_gbps": 0.5,
    "rpc_shm_payload_gbps": 0.5,
    "allreduce_tree_gbps": 0.5,
    "batcher_fill_s": 0.5,
    "envpool_steps_per_s": 0.4,
    # Kill-to-recovery is dominated by worker-process spawn (a fresh
    # interpreter importing the env module) — highly host-load bound.
    "envpool_recovery_s": 0.65,
    "serial_encode_gbps": 0.65,
    "serial_decode_gbps": 0.65,
    # Pickle + sha256 + fsync'd disk writes + RPC push: every noise
    # source the serial and rpc rows see, plus the disk.
    "statestore_replicate_gbps": 0.65,
    # Serving tier: a threaded closed-loop through router + 2 replicas —
    # every scheduling noise source above compounds here, and p99 is a
    # tail statistic on top of it (observed swinging ~2x run-to-run on
    # the shared container).
    "serving_qps": 0.5,
    "serving_p99_latency_s": 0.65,
    # One canary rollout end to end: floored by the fixed settle window,
    # but the machinery around it (publish acks, gate evaluation ticks,
    # threaded load) rides the same shared-container scheduling noise as
    # the serving rows.
    "fleet_rollout_s": 0.65,
}


def _result(name: str, value, unit, direction, smoke, stats=None,
            telemetry=None, extra=None, error=None) -> BenchResult:
    return BenchResult(
        metric=name, value=value, unit=unit, direction=direction,
        suite=SUITE_NAME, smoke=smoke, cmd=_cmd(name, smoke),
        stats=stats or {}, telemetry=telemetry, extra=extra or {},
        error=error, tol=TREND_TOLERANCE.get(name),
    )


def _compact_summary(s):
    """Round one stepscope loop summary down to a row-sized attachment."""
    return {
        "steps": s["steps"],
        "wall_s": round(s["wall_s"], 6),
        "phases": {k: round(v, 6) for k, v in s["phases"].items()},
        "fractions": {k: round(v, 6) for k, v in s["fractions"].items()},
    }


def _stepscope_extra(snapshot, loop):
    """Compact phase-ledger attachment for a row's ``extra``: the named
    loop's per-phase seconds and derived fractions reconstructed from a
    registry snapshot (None when the loop never recorded a step)."""
    from ..telemetry import summarize_stepscope

    s = summarize_stepscope(snapshot).get(loop)
    return None if s is None else _compact_summary(s)


# -- RPC echo + payload -------------------------------------------------------


def _echo_cohort(transports=None):
    from ..rpc import Rpc
    from ..telemetry import Telemetry
    from ..utils import set_log_level

    set_log_level("error")
    # ONE shared Telemetry for both peers (gauges are peer-labelled for
    # exactly this case), so the attached snapshot carries the client's
    # rpc_client_latency_seconds AND the server's rpc_server_handle_seconds
    # — the budget layer gates both sides of the call.
    tel = Telemetry("perfwatch-echo")
    a = Rpc("perfwatch-client", telemetry=tel)
    b = Rpc("perfwatch-server", telemetry=tel)
    if transports is not None:
        # Pin the lane under test: the TCP baseline row must not let the
        # same-host shm lane silently carry its payloads (and vice versa
        # the shm row asserts its bytes really rode shm).
        a.set_transports(transports)
        b.set_transports(transports)
    b.define("echo", lambda x: x)
    b.listen("127.0.0.1:0")  # OS-assigned: parallel CI jobs must coexist
    a.connect(b.debug_info()["listen"][0])
    return a, b


def bench_rpc_echo(smoke: bool) -> BenchResult:
    """Per-call latency of a loopback echo — the RPC dispatch floor."""
    repeats = 150 if smoke else 500
    a, b = _echo_cohort()
    try:
        samples = measure(
            lambda: a.sync("perfwatch-server", "echo", 1),
            warmup=20, repeats=repeats,
        )
        stats = trimmed_stats(samples)
        stats["samples"] = stats["samples"][:16]  # keep trend rows small
        return _result(
            "rpc_echo_latency_s", stats["median"], "s/call", "lower",
            smoke, stats=stats, telemetry=b.telemetry.snapshot(),
        )
    finally:
        a.close()
        b.close()


#: Concurrent in-flight echoes per payload-throughput rep: throughput
#: benchmarks measure the pipelined regime (gradient pushes, rollout
#: uploads, allreduce chunks all overlap calls), not serial round-trip
#: latency — that's rpc_echo_latency_s's job.
_PAYLOAD_DEPTH = 4


def _payload_rep(a, arr, depth=_PAYLOAD_DEPTH):
    futs = [a.async_("perfwatch-server", "echo", arr)
            for _ in range(depth)]
    for f in futs:
        f.result(120)


def bench_rpc_payload(smoke: bool) -> BenchResult:
    """Pipelined round-trip throughput of large tensor payloads through
    the RPC plane over loopback TCP (depth-4 concurrent echoes; each
    rep moves 2 x depth x the array bytes)."""
    nbytes = (4 << 20) if smoke else (32 << 20)
    repeats = 4 if smoke else 8
    arr = np.ones(nbytes // 4, np.float32)
    a, b = _echo_cohort(transports={"tcp"})
    try:
        samples = measure(
            lambda: _payload_rep(a, arr), warmup=2, repeats=repeats,
        )
        stats = trimmed_stats(samples)
        gbps = 2 * nbytes * _PAYLOAD_DEPTH / stats["median"] / 1e9
        return _result(
            "rpc_payload_gbps", gbps, "GB/s", "higher", smoke,
            stats=stats, telemetry=b.telemetry.snapshot(),
            extra={"payload_mb": round(nbytes / 1e6, 1),
                   "depth": _PAYLOAD_DEPTH},
        )
    finally:
        a.close()
        b.close()


def bench_rpc_shm_payload(smoke: bool) -> BenchResult:
    """The rpc_payload pipelined echo over the same-host shm ring lane
    (spill-slot writes on the sender, zero-copy mapped receive) — the
    PR-14 acceptance row, compared against ``rpc_payload_gbps``. The
    row errors (null value) if the payloads did not actually ride the
    lane — a silent TCP fallback must never masquerade as an shm
    measurement; ``extra`` carries the measured shm byte count as
    evidence."""
    nbytes = (4 << 20) if smoke else (32 << 20)
    repeats = 4 if smoke else 8
    arr = np.ones(nbytes // 4, np.float32)
    a, b = _echo_cohort(transports={"tcp", "shm"})
    try:
        # The lane rendezvous rides the greeting + one offer/accept RTT.
        a.sync("perfwatch-server", "echo", 1)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            peer = a._peers.get("perfwatch-server")
            if peer and "shm" in peer.conns:
                break
            time.sleep(0.02)
        else:
            raise RuntimeError("shm lane never came up on loopback")
        reg = a.telemetry.registry
        base_shm = reg.value("rpc_bytes_out_total", transport="shm") or 0
        warmup = 2  # also settles lane EWMAs
        samples = measure(
            lambda: _payload_rep(a, arr), warmup=warmup, repeats=repeats,
        )
        shm_bytes = (
            reg.value("rpc_bytes_out_total", transport="shm") or 0
        ) - base_shm
        # shm_bytes accumulated across warmup reps too (the snapshot
        # predates measure()), so count them in `sent` — else the 0.8
        # headroom silently loosens to ~0.5 and a run where half the
        # measured-phase payloads fell back to TCP still passes.
        sent = (repeats + warmup) * _PAYLOAD_DEPTH * nbytes
        if shm_bytes < 0.8 * sent:  # headroom: the 5% exploration bandit
            raise RuntimeError(
                f"payloads fell back to TCP mid-run ({shm_bytes} shm "
                f"bytes for {sent} sent)"
            )
        stats = trimmed_stats(samples)
        gbps = 2 * nbytes * _PAYLOAD_DEPTH / stats["median"] / 1e9
        return _result(
            "rpc_shm_payload_gbps", gbps, "GB/s", "higher", smoke,
            stats=stats, telemetry=b.telemetry.snapshot(),
            extra={"payload_mb": round(nbytes / 1e6, 1),
                   "depth": _PAYLOAD_DEPTH,
                   "shm_bytes_out": int(shm_bytes)},
        )
    finally:
        a.close()
        b.close()


# -- loopback tree allreduce --------------------------------------------------


def bench_allreduce_tree(smoke: bool) -> BenchResult:
    """4-peer in-process Group tree allreduce over loopback TCP — the
    Accumulator's DCN reduce plane with the wire taken out, so what
    remains is serialization + copy + protocol cost."""
    from ..rpc import Rpc
    from ..rpc.broker import Broker
    from ..rpc.group import Group
    from ..utils import set_log_level

    set_log_level("error")
    n_peers = 4
    nbytes = (256 << 10) if smoke else (4 << 20)
    rounds = 3 if smoke else 6

    broker_rpc = Rpc("perfwatch-broker")
    broker_rpc.listen("127.0.0.1:0")
    addr = broker_rpc.debug_info()["listen"][0]
    broker = Broker(broker_rpc)
    stop = threading.Event()

    def pump_broker():
        while not stop.is_set():
            broker.update()
            time.sleep(0.02)

    threading.Thread(target=pump_broker, daemon=True).start()

    rpcs, groups = [], []
    try:
        for i in range(n_peers):
            r = Rpc(f"perfwatch-ar-{i}")
            r.listen("127.0.0.1:0")
            r.connect(addr)
            g = Group(r, group_name="perfwatch",
                      broker_name="perfwatch-broker", timeout=120.0)
            rpcs.append(r)
            groups.append(g)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            for g in groups:
                g.update()
            if all(len(g.members) == n_peers and g.active() for g in groups):
                break
            time.sleep(0.02)
        else:
            raise RuntimeError("group never stabilized")

        def pump():
            while not stop.is_set():
                for g in groups:
                    g.update()
                time.sleep(0.05)

        threading.Thread(target=pump, daemon=True).start()

        data = [np.full(nbytes // 4, float(i), np.float32)
                for i in range(n_peers)]

        def one_round(tag):
            futs = [g.all_reduce(tag, d) for g, d in zip(groups, data)]
            res = [f.result(timeout=120) for f in futs]
            assert abs(float(res[0][0]) - sum(range(n_peers))) < 1e-5
            return res

        one_round("warm")
        samples = []
        for r in range(rounds):
            t0 = clock()
            one_round(f"r{r}")
            samples.append(clock() - t0)
        stats = trimmed_stats(samples)
        # Algorithm bandwidth (bench_allreduce.py convention): each peer
        # contributes + receives the full buffer once per round.
        gbps = nbytes * n_peers / stats["median"] / 1e9
        return _result(
            "allreduce_tree_gbps", gbps, "GB/s", "higher", smoke,
            stats=stats, telemetry=rpcs[0].telemetry.snapshot(),
            extra={"peers": n_peers, "mb": round(nbytes / 1e6, 2)},
        )
    finally:
        stop.set()
        for g in groups:
            g.close()
        for r in rpcs:
            r.close()
        broker_rpc.close()


# -- batcher fill -------------------------------------------------------------


def bench_batcher_fill(smoke: bool) -> BenchResult:
    """First-item-to-emitted-batch latency of the two-stage Batcher — the
    acting plane's staging cost at trivial item size."""
    from ..ops.batcher import Batcher
    from ..telemetry import global_telemetry

    bs = 64
    repeats = 20 if smoke else 60
    item = {"obs": np.zeros((4, 4), np.float32), "r": np.float32(0.0)}
    batcher = Batcher(bs, name="perfwatch")
    try:
        def fill_one():
            for _ in range(bs):
                batcher.stack(item)
            batcher.get(timeout=10)

        samples = measure(fill_one, warmup=2, repeats=repeats)
        stats = trimmed_stats(samples)
        stats["samples"] = stats["samples"][:16]
        snap = global_telemetry().snapshot()
        return _result(
            "batcher_fill_s", stats["median"], "s/batch", "lower", smoke,
            stats=stats, telemetry=snap, extra={"batch_size": bs},
        )
    finally:
        batcher.close()


# -- envpool ------------------------------------------------------------------


class TrivialEnv:
    """Near-zero-cost env (module-level so it pickles into spawn
    workers): the benchmark measures pool machinery, not env physics."""

    def __init__(self, seed: int):
        self.seed = seed
        self.obs = np.array([seed, 0.0], np.float32)

    def reset(self):
        return self.obs, {}

    def step(self, action):
        return self.obs, 0.0, False, False, {}

    def close(self):
        pass


def _envpool_rate(pool, bs: int, n: int) -> float:
    """Double-buffered env-steps/s over ``n`` loop iterations."""
    a = np.zeros(bs, np.int64)
    for b in (0, 1):
        pool.step(b, a).result(30)
    t0 = clock()
    f0 = pool.step(0, a)
    f1 = pool.step(1, a)
    for _ in range(n):
        f0.result(30)
        f0 = pool.step(0, a)
        f1.result(30)
        f1 = pool.step(1, a)
    f0.result(30)
    f1.result(30)
    return (2 * n + 2) * bs / (clock() - t0)


def bench_envpool_steps(smoke: bool) -> BenchResult:
    """Double-buffered trivial-env steps/s through the full EnvPool
    dispatch path (slab writes, ring dispatch, worker step loop).

    Also measures the SUPERVISION overhead on the healthy path (the
    headline pool runs with the default supervisor; a second pool runs
    ``supervise=False``): interleaved best-of passes per mode, ratio in
    ``extra["supervision_overhead_frac"]`` — budget-gated < 5%
    (docs/perf.md). Best-of is used because the overhead question is
    structural (heartbeat writes, mark scans), not a load statistic."""
    from ..envpool import EnvPool
    from ..telemetry import global_telemetry

    bs = 64 if smoke else 128
    n = 100 if smoke else 400
    pool = EnvPool(TrivialEnv, num_processes=1, batch_size=bs,
                   num_batches=2, name="perfwatch-sup")
    raw = EnvPool(TrivialEnv, num_processes=1, batch_size=bs,
                  num_batches=2, supervise=False, name="perfwatch-raw")
    try:
        value = _envpool_rate(pool, bs, n)
        # Supervision-overhead A/B: interleaved so host noise hits both
        # modes alike; best-of per mode answers the structural question.
        m = max(10, n // 4)
        sup_best = raw_best = 0.0
        for _ in range(3):
            sup_best = max(sup_best, _envpool_rate(pool, bs, m))
            raw_best = max(raw_best, _envpool_rate(raw, bs, m))
        overhead = max(0.0, 1.0 - sup_best / raw_best)
        batches = 2 * n + 2
        dt = batches * bs / value
        snap = global_telemetry().snapshot()
        # The pools' built-in StepScopes already attributed every batch
        # (env_wait / staging / batch_fill) into the global registry;
        # pin the composition snapshot to the row so the perf ledger
        # shows WHERE the batch time went, not just the rate.
        stepscope = _stepscope_extra(snap, "envpool")
        return _result(
            "envpool_steps_per_s", value, "env-steps/s",
            "higher", smoke,
            stats={"n": batches, "mean": dt / batches, "total_s": dt},
            telemetry=snap,
            extra={"batch_size": bs, "procs": 1,
                   "supervision_overhead_frac": round(overhead, 4),
                   "supervised_best": sup_best,
                   "unsupervised_best": raw_best,
                   "stepscope": stepscope},
        )
    finally:
        pool.close()
        raw.close()


def bench_envpool_recovery(smoke: bool) -> BenchResult:
    """Kill-to-first-post-respawn-step wall time: SIGKILL one worker of a
    supervised pool, then drive retries until a step completes — the
    env-tier failover budget (detection + respawn + handshake + retry).
    Dominated by worker-process spawn (a fresh interpreter importing the
    env module), so the budget is a catastrophe guard, not a latency
    target."""
    import os
    import signal as _signal

    from ..envpool import EnvPool, WorkerDied
    from ..telemetry import global_telemetry

    bs = 8
    reps = 2 if smoke else 3
    pool = EnvPool(TrivialEnv, num_processes=2, batch_size=bs,
                   num_batches=1, restart_backoff=0.05,
                   name="perfwatch-recovery")
    try:
        a = np.zeros(bs, np.int64)
        pool.step(0, a).result(30)
        samples = []
        for r in range(reps):
            victim = r % 2
            t0 = clock()
            os.kill(pool._procs[victim].pid, _signal.SIGKILL)
            while True:
                try:
                    pool.step(0, a).result(30)
                    break
                except WorkerDied:
                    time.sleep(0.01)
            samples.append(clock() - t0)
        stats = trimmed_stats(samples)
        snap = global_telemetry().snapshot()
        return _result(
            "envpool_recovery_s", stats["median"], "s", "lower", smoke,
            stats=stats, telemetry=snap,
            extra={"procs": 2, "reps": reps},
        )
    finally:
        pool.close()


# -- serial encode / decode ---------------------------------------------------


def _serial_payload(nbytes: int):
    return {
        "obs": np.arange(nbytes // 4, dtype=np.float32),
        "meta": {"step": 7, "done": False, "tag": "perfwatch"},
        "rewards": [1.0, 2.0, 3.0],
    }


def bench_serial_encode(smoke: bool) -> BenchResult:
    """serialize() throughput on a tensor-bearing payload (zero-copy
    framing: the cost is metadata encoding + iovec assembly)."""
    from ..rpc import serial

    nbytes = (4 << 20) if smoke else (32 << 20)
    repeats = 10 if smoke else 30
    obj = _serial_payload(nbytes)
    total = serial.frames_len(serial.serialize(1, 2, obj))
    samples = measure(
        lambda: serial.serialize(1, 2, obj), warmup=2, repeats=repeats
    )
    stats = trimmed_stats(samples)
    return _result(
        "serial_encode_gbps", total / stats["median"] / 1e9, "GB/s",
        "higher", smoke, stats=stats,
        extra={"frame_mb": round(total / 1e6, 1)},
    )


def bench_serial_decode(smoke: bool) -> BenchResult:
    """deserialize_body() throughput on the same payload (zero-copy
    views over an aligned receive buffer). ``extra`` carries the A/B
    against the forced-copy arm (``copy_tensors=True``, the
    pre-zero-copy behavior): ``copy_decode_gbps`` and the resulting
    ``zero_copy_speedup`` — direct evidence the multi-MB tensor copy is
    gone from the receive path."""
    from ..rpc import serial

    nbytes = (4 << 20) if smoke else (32 << 20)
    repeats = 10 if smoke else 30
    frames = serial.serialize(1, 2, _serial_payload(nbytes))
    wire = b"".join(bytes(f) for f in frames)
    body_arr = serial.alloc_aligned(len(wire) - serial.HEADER.size)
    body_arr[:] = np.frombuffer(wire, np.uint8)[serial.HEADER.size:]
    body = memoryview(body_arr)
    total = len(wire)

    def decode():
        rid, fid, obj = serial.deserialize_body(body)
        assert rid == 1 and fid == 2
        return obj

    samples = measure(decode, warmup=2, repeats=repeats)
    stats = trimmed_stats(samples)
    value = total / stats["median"] / 1e9
    # A/B control arm: same frame, tensors force-copied out.
    copy_samples = measure(
        lambda: serial.deserialize_body(body, copy_tensors=True),
        warmup=1, repeats=max(3, repeats // 2),
    )
    copy_gbps = total / trimmed_stats(copy_samples)["median"] / 1e9
    return _result(
        "serial_decode_gbps", value, "GB/s",
        "higher", smoke, stats=stats,
        extra={"frame_mb": round(total / 1e6, 1),
               "copy_decode_gbps": round(copy_gbps, 3),
               "zero_copy_speedup": round(value / copy_gbps, 2)},
    )


# -- durable state (statestore) -----------------------------------------------


def bench_statestore_replicate(smoke: bool) -> BenchResult:
    """Durable-state publish throughput: one committed model version
    through the full replication pipeline — encode, chunk + per-chunk
    sha256, crash-atomic local write (fsync'd staging + rename), then
    the offer/ingest/commit push to one loopback replica. GB/s of state
    made peer-durable; the CPU proxy under the ``ss_publish`` ->
    ``ss_replicate`` path the host-loss scenario depends on."""
    import tempfile

    from ..statestore import StateStore

    nbytes = (4 << 20) if smoke else (16 << 20)
    repeats = 4 if smoke else 8
    state = {"w": np.ones(nbytes // 4, np.float32)}
    a, b = _echo_cohort()
    version = [0]
    with tempfile.TemporaryDirectory() as td:
        store_a = StateStore(td + "/a", a, keep_versions=2, name="bench-a")
        store_b = StateStore(td + "/b", b, keep_versions=2, name="bench-b")
        try:

            def rep():
                version[0] += 1
                acks = store_a.publish(version[0], state,
                                       peers=("perfwatch-server",))
                if not all(acks.values()):
                    raise RuntimeError(f"publish not fully acked: {acks}")

            samples = measure(rep, warmup=1, repeats=repeats)
            stats = trimmed_stats(samples)
            gbps = nbytes / stats["median"] / 1e9
            return _result(
                "statestore_replicate_gbps", gbps, "GB/s", "higher",
                smoke, stats=stats, telemetry=a.telemetry.snapshot(),
                extra={"payload_mb": round(nbytes / 1e6, 1),
                       "versions": version[0]},
            )
        finally:
            store_a.close()
            store_b.close()
            a.close()
            b.close()


# -- serving tier -------------------------------------------------------------

#: One serving load run feeds BOTH serving rows (the cohort costs ~2s to
#: stand up; qps and p99 are two views of the same closed loop). Keyed by
#: smoke flag; populated by whichever serving bench runs first in this
#: process, so ``--only serving_p99_latency_s`` still works.
_SERVING_CACHE: Dict[bool, Dict] = {}


def _serving_load(smoke: bool) -> Dict:
    """Closed-loop load through a router + 2 in-process replicas with a
    jitted (padded, compile-once) matmul model — the serving tier's full
    hot path: admission, dynamic batching, deadline propagation,
    load-aware dispatch."""
    import jax

    from ..rpc import Rpc
    from ..serving import Replica, Router
    from ..utils import set_log_level

    set_log_level("error")
    n_requests = 240 if smoke else 1200
    concurrency = 8
    batch_size = 8
    params = {"w": (np.eye(16) * 2.0).astype(np.float32)}
    model = jax.jit(lambda p, x: x @ p["w"])
    rpcs, reps = [], []
    router_rpc = None
    router = None
    try:
        for i in range(2):
            r = Rpc(f"perfwatch-rep{i}")
            r.listen("127.0.0.1:0")  # OS-assigned: parallel CI jobs coexist
            reps.append(Replica(r, model, params, version=1,
                                batch_size=batch_size, pad=True))
            rpcs.append(r)
        router_rpc = Rpc("perfwatch-router")
        for r in rpcs:
            router_rpc.connect(r.debug_info()["listen"][0])
        router = Router(router_rpc, [r.get_name() for r in rpcs],
                        probe_interval_s=0.1, attempt_timeout_s=5.0,
                        seed=0)
        deadline = clock() + 30
        while len(router.routable()) < 2:
            if clock() > deadline:
                raise RuntimeError("serving fleet never became routable")
            time.sleep(0.02)
        x = np.ones(16, np.float32)
        for _ in range(2 * batch_size):  # compile both pad shapes + warm
            router.infer(x, budget_s=30.0)

        lock = threading.Lock()
        latencies: list = []
        errors: list = []
        per = n_requests // concurrency

        def worker():
            for _ in range(per):
                t1 = clock()
                try:
                    router.infer(x, budget_s=30.0)
                except (asyncio.CancelledError,
                        concurrent.futures.CancelledError):
                    raise  # never swallow task cancellation
                except Exception as e:
                    with lock:
                        errors.append(f"{type(e).__name__}: {e}")
                    continue
                dt = clock() - t1
                with lock:
                    latencies.append(dt)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(concurrency)]
        t0 = clock()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        wall = clock() - t0
        if errors or len(latencies) != per * concurrency:
            raise RuntimeError(
                f"serving load errored: {len(errors)} failures "
                f"(first: {errors[:1]})"
            )
        latencies.sort()
        return {
            "qps": len(latencies) / wall,
            "p99_s": latencies[min(int(0.99 * len(latencies)),
                                   len(latencies) - 1)],
            "p50_s": latencies[len(latencies) // 2],
            "requests": len(latencies),
            "concurrency": concurrency,
            "telemetry": router_rpc.telemetry.snapshot(),
        }
    finally:
        if router is not None:
            router.close()
        if router_rpc is not None:
            router_rpc.close()
        for rep in reps:
            rep.close()
        for r in rpcs:
            r.close()


def _serving_cached(smoke: bool) -> Dict:
    run = _SERVING_CACHE.get(smoke)
    if run is None:
        run = _serving_load(smoke)
        _SERVING_CACHE[smoke] = run
    return run


def bench_serving_qps(smoke: bool) -> BenchResult:
    """Closed-loop serving throughput (router + 2 replicas, batched
    jitted model) — requests/s across 8 concurrent callers."""
    run = _serving_cached(smoke)
    return _result(
        "serving_qps", run["qps"], "req/s", "higher", smoke,
        stats={"n": run["requests"], "p50": run["p50_s"],
               "p99": run["p99_s"]},
        telemetry=run["telemetry"],
        extra={"concurrency": run["concurrency"], "replicas": 2},
    )


def bench_serving_p99(smoke: bool) -> BenchResult:
    """End-to-end p99 request latency of the same serving load — the
    tail the robustness layer exists to keep bounded."""
    run = _serving_cached(smoke)
    return _result(
        "serving_p99_latency_s", run["p99_s"], "s", "lower", smoke,
        stats={"n": run["requests"], "p50": run["p50_s"]},
        telemetry=run["telemetry"],
        extra={"concurrency": run["concurrency"], "replicas": 2},
    )


# -- fleet tier ---------------------------------------------------------------


def bench_fleet_rollout(smoke: bool) -> BenchResult:
    """Wall time of one zero-downtime canary rollout (canary publish ->
    weighted settle -> promote) through a ``FleetSpec.small`` cohort
    under closed-loop load. The 0.5s settle window is a constant floor;
    the row watches the control-plane machinery around it — spec
    materialization is excluded, dropped requests turn the row into an
    error row."""
    from ..fleet import FleetSpec
    from ..testing.scenarios import FleetHarness, _run_load
    from ..utils import set_log_level

    set_log_level("error")
    settle_s = 0.5
    spec = FleetSpec.small(replicas=3, routers=1, learners=0,
                           env_workers=0, settle_s=settle_s)
    n_requests = 160 if smoke else 640
    harness = FleetHarness(spec, standby=False)
    lock = threading.Lock()
    try:
        harness.wait_routable(3)
        ctl = harness.controller
        ctl.publish_model({"scale": np.float32(3.0)}, 2)
        outcomes: list = []
        threads = _run_load(harness.router, n_requests, 4, 8.0,
                            outcomes, lock)
        t0 = clock()
        state = ctl.start_rollout(version=2, wait=True)
        dt = clock() - t0
        for t in threads:
            t.join(timeout=120)
        if state != "promoted":
            raise RuntimeError(f"rollout ended {state}, not promoted")
        bad = [r for r in outcomes if r[0] != "ok"]
        if bad:
            raise RuntimeError(
                f"rollout dropped {len(bad)} accepted requests "
                f"(first: {bad[:1]})"
            )
        return _result(
            "fleet_rollout_s", dt, "s", "lower", smoke,
            stats={"settle_s": settle_s, "requests": len(outcomes)},
            telemetry=ctl.rpc.telemetry.snapshot(),
            extra={"replicas": 3,
                   "canary_weight": spec.rollout.canary_weight},
        )
    finally:
        harness.close()


# -- registry -----------------------------------------------------------------

CPU_PROXY_SUITE: Dict[str, Callable[[bool], BenchResult]] = {
    "rpc_echo_latency_s": bench_rpc_echo,
    "rpc_payload_gbps": bench_rpc_payload,
    "rpc_shm_payload_gbps": bench_rpc_shm_payload,
    "allreduce_tree_gbps": bench_allreduce_tree,
    "batcher_fill_s": bench_batcher_fill,
    "envpool_steps_per_s": bench_envpool_steps,
    "envpool_recovery_s": bench_envpool_recovery,
    "serial_encode_gbps": bench_serial_encode,
    "serial_decode_gbps": bench_serial_decode,
    "statestore_replicate_gbps": bench_statestore_replicate,
    "serving_qps": bench_serving_qps,
    "serving_p99_latency_s": bench_serving_p99,
    "fleet_rollout_s": bench_fleet_rollout,
}


def run_suite(
    *,
    smoke: bool = False,
    only: Optional[List[str]] = None,
    max_seconds: Optional[float] = None,
    log: Callable[[str], None] = lambda s: None,
) -> List[BenchResult]:
    """Run the suite in declaration order. A benchmark that raises is
    recorded as a null-value row (error string, no value) rather than
    aborting the run; once ``max_seconds`` of wall clock is spent,
    remaining benchmarks are recorded as wall-clock-cap nulls so the CI
    stage stays bounded and the skip is on the record."""
    names = list(CPU_PROXY_SUITE)
    if only:
        unknown = set(only) - set(names)
        if unknown:
            raise ValueError(f"unknown benchmark(s): {sorted(unknown)}")
        names = [n for n in names if n in set(only)]
    t0 = clock()
    out: List[BenchResult] = []
    for name in names:
        if max_seconds is not None and clock() - t0 > max_seconds:
            out.append(_result(
                name, None, "", "higher", smoke,
                error=f"skipped: suite wall-clock cap {max_seconds}s "
                f"exhausted after {clock() - t0:.1f}s",
            ))
            continue
        log(f"running {name} ({'smoke' if smoke else 'full'}) ...")
        t1 = clock()
        try:
            r = CPU_PROXY_SUITE[name](smoke)
        except (asyncio.CancelledError, concurrent.futures.CancelledError):
            raise  # never swallow task cancellation
        except Exception as e:
            r = _result(
                name, None, "", "higher", smoke,
                error=f"{type(e).__name__}: {e}"[:500],
            )
        log(f"  {name}: "
            + (f"{r.value:.6g} {r.unit}" if r.value is not None
               else f"NULL ({r.error})")
            + f" [{clock() - t1:.1f}s]")
        out.append(r)
    return out
