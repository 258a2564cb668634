"""Dependency-free API documentation generator.

Docs parity with the reference's sphinx tree (reference:
docs/source/index.rst lists the moolib Python API page-by-page). This build
environment has no sphinx, so the generator walks the live package with
``inspect`` and emits GitHub-renderable markdown under ``docs/api/`` plus a
``docs/index.md`` module inventory. The CI docs job runs it with ``--check``
to fail when committed docs drift from the code.

Usage:
    python tools/gen_api_docs.py            # (re)write docs/
    python tools/gen_api_docs.py --check    # exit 1 if docs are stale
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
DOCS = os.path.join(ROOT, "docs")

# Module inventory: (import path, one-line role). Mirrors the layering in
# SURVEY.md §1 / moolib_tpu/__init__.py.
MODULES = [
    ("moolib_tpu", "package surface: reference-parity exports"),
    ("moolib_tpu.rpc.rpc", "named-peer RPC core: reliability, discovery, "
     "transports, dynamic batching"),
    ("moolib_tpu.rpc.serial", "binary wire serialization, zero-copy tensor "
     "framing"),
    ("moolib_tpu.rpc.shmring", "same-host shared-memory ring transport: "
     "SPSC rings, spill slots, pipe doorbells"),
    ("moolib_tpu.rpc.broker", "cohort membership authority"),
    ("moolib_tpu.rpc.group", "group membership view + DCN tree allreduce"),
    ("moolib_tpu.rpc.faults", "fault-injection hook contract for the RPC "
     "wire seams"),
    ("moolib_tpu.telemetry", "unified telemetry: metrics registry + trace "
     "spans + the __telemetry scrape surface"),
    ("moolib_tpu.telemetry.registry", "counters, gauges, fixed-log-bucket "
     "histograms; JSON/Prometheus exports"),
    ("moolib_tpu.telemetry.trace", "bounded span buffer with "
     "Chrome-trace/Perfetto export"),
    ("moolib_tpu.flightrec", "black-box flight recorder + cross-peer "
     "incident bundles for post-mortem debugging"),
    ("moolib_tpu.flightrec.events", "typed flight-event schema (kinds + "
     "field contracts)"),
    ("moolib_tpu.flightrec.recorder", "bounded ring of typed, "
     "timestamped state-transition events"),
    ("moolib_tpu.flightrec.bundle", "versioned on-disk incident bundles "
     "with strict schema validation"),
    ("moolib_tpu.flightrec.capture", "incident triggers, rate-limited "
     "auto-capture, bundle freezing"),
    ("moolib_tpu.flightrec.merge", "clock-offset estimation + "
     "causally-ordered cross-peer timeline merge"),
    ("moolib_tpu.flightrec.crawl", "the one cohort-crawl implementation "
     "shared by the dump/report tools"),
    ("moolib_tpu.statestore", "peer-replicated durable training state: "
     "content-hashed bundles, restore negotiation, async replication"),
    ("moolib_tpu.statestore.bundle", "on-disk bundle format: chunked, "
     "per-chunk sha256, crash-atomic stage+rename writes"),
    ("moolib_tpu.statestore.store", "StateStore wire family + restore "
     "negotiation + the Accumulator-attached Replicator"),
    ("moolib_tpu.testing.chaos", "chaosnet: deterministic seeded fault "
     "injection (FaultPlan engine + ChaosNet installer)"),
    ("moolib_tpu.testing.scenarios", "canonical chaos scenarios shared by "
     "the tier-1 suite and the CI soak runner"),
    ("moolib_tpu.testing.locktrace", "dynamic lock-order tracer: "
     "instrumented locks, observed acquires-while-holding graph"),
    ("moolib_tpu.testing.restrack", "dynamic resource-leak tracker: "
     "acquisition/release pairing for threads, shm, Rpcs, gauges "
     "(lifelint's runtime mirror)"),
    ("moolib_tpu.testing.hotwatch", "dynamic transfer/compile gate: "
     "counted D2H/H2D window with staged-copy accounting and compile "
     "flatness (hotlint's runtime mirror)"),
    ("moolib_tpu.testing.paritywatch", "bitwise-replay gate: N-run "
     "pytree parity with first-divergent-leaf/ULP reporting + allreduce "
     "arrival-order invariance (numlint's runtime mirror)"),
    ("moolib_tpu.serving", "fault-tolerant serving tier: replicated "
     "inference behind a load-aware router"),
    ("moolib_tpu.serving.admission", "bounded admission queues, "
     "deadline-aware shedding, graceful drain"),
    ("moolib_tpu.serving.health", "probe-miss gating + failure-rate "
     "circuit breaker for routed replicas"),
    ("moolib_tpu.serving.replica", "model replica: admission-controlled "
     "dynamic batching in jit, hot model swap"),
    ("moolib_tpu.serving.router", "load-aware dispatch, deadline "
     "propagation, replica failover and retry safety"),
    ("moolib_tpu.fleet.spec", "declarative cohort shape: validated, "
     "JSON-round-trippable FleetSpec tree"),
    ("moolib_tpu.fleet.controller", "fleet controller: materialization, "
     "restart-budget supervision, epoch-fenced standby adoption"),
    ("moolib_tpu.fleet.rollout", "canary rollout state machine with "
     "SLO-gated auto-promote/auto-rollback"),
    ("moolib_tpu.fleet.runner", "subprocess role entrypoint "
     "(python -m moolib_tpu.fleet.runner)"),
    ("moolib_tpu.parallel.accumulator", "elastic data-parallel gradient "
     "accumulation (ICI psum + DCN tree)"),
    ("moolib_tpu.parallel.mesh", "device mesh construction and batch "
     "sharding"),
    ("moolib_tpu.parallel.tp", "tensor parallelism (Megatron-style "
     "NamedSharding specs)"),
    ("moolib_tpu.parallel.pipeline", "pipeline parallelism"),
    ("moolib_tpu.parallel.moe", "sparse experts (the dropless top-k layer)"),
    ("moolib_tpu.parallel.distributed", "multi-controller process groups "
     "over ICI/DCN"),
    ("moolib_tpu.parallel.stats", "cluster-wide stats reduction"),
    ("moolib_tpu.envpool.pool", "multi-process env execution over shared "
     "memory"),
    ("moolib_tpu.envpool.stepper", "multi-client env serving over RPC"),
    ("moolib_tpu.ops.batcher", "dynamic nested-tensor batcher with H2D "
     "staging"),
    ("moolib_tpu.ops.vtrace", "V-trace off-policy corrections"),
    ("moolib_tpu.ops.embed", "embedding lookup; the table's gradient as a "
     "blocked one-hot product on the MXU for small narrow tables"),
    ("moolib_tpu.ops.hyper_mix", "the residual mixing of several streams as "
     "fused Pallas passes, forward and backward"),
    ("moolib_tpu.ops.attention", "dense/blockwise/flash attention (pallas "
     "kernels)"),
    ("moolib_tpu.ops.ring_attention", "ring + zigzag sequence-parallel "
     "attention"),
    ("moolib_tpu.ops.batchsizefinder", "latency-aware batch-size search"),
    ("moolib_tpu.models.impala", "IMPALA ResNet torso"),
    ("moolib_tpu.models.a2c", "A2C MLP/LSTM nets"),
    ("moolib_tpu.models.transformer", "transformer with sequence-parallel "
     "attention"),
    ("moolib_tpu.models.nethack", "NetHack dict-obs model"),
    ("moolib_tpu.models.lm", "decoder language model as a token-level "
     "agent: layer list, windowed grouped-head attention, dropless experts"),
    ("moolib_tpu.learner", "jitted IMPALA train step + train state"),
    ("moolib_tpu.utils.checkpoint", "atomic checkpoint/resume"),
    ("moolib_tpu.utils.diskio", "crash-atomic disk writes + the "
     "injectable disk-fault seam"),
    ("moolib_tpu.utils.profiling", "XLA profiler capture"),
    ("moolib_tpu.utils.flops", "analytic FLOPs accounting / MFU"),
    ("moolib_tpu.utils.nest", "nested-structure utilities"),
    ("moolib_tpu.analysis", "moolint: async-RPC safety, JAX trace hygiene, "
     "sharding/collective consistency, RPC round-balance, race/lock-order, "
     "resource-lifecycle, hot-path device/host discipline + "
     "numerics/determinism static analysis (tier-1 enforced)"),
    ("moolib_tpu.analysis.rules_num", "numlint rule family: PRNG key "
     "discipline, seeded randomness, fp32 accumulation, dtype promotion, "
     "iteration-order determinism"),
    ("moolib_tpu.bench.harness", "perfwatch harness: timing protocol + "
     "unified result schema"),
    ("moolib_tpu.bench.suite", "CPU-proxy perf suite (host plane; runs on "
     "every PR)"),
    ("moolib_tpu.bench.trends", "append-only trend store + noise-aware "
     "regression detector"),
    ("moolib_tpu.bench.budgets", "absolute perf guardrails from telemetry "
     "histogram quantiles"),
    ("moolib_tpu.broker", "broker CLI (python -m moolib_tpu.broker)"),
]


def _signature(obj) -> str:
    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # Default values whose repr embeds a memory address (functions, bound
    # methods in flax dataclass fields) would make the output
    # non-deterministic across runs.
    return re.sub(r" at 0x[0-9a-fA-F]+", "", sig)


def _first_para(doc: str) -> str:
    # flax dataclass docstrings embed constructor reprs with memory
    # addresses; scrub them for deterministic output.
    return re.sub(r" at 0x[0-9a-fA-F]+", "", (doc or "").strip())


def _doc_module(path: str, role: str) -> str:
    mod = importlib.import_module(path)
    lines = [f"# `{path}`", "", f"*{role}*", ""]
    if mod.__doc__:
        lines += [_first_para(mod.__doc__), ""]
    members = []
    for name, obj in sorted(vars(mod).items()):
        if name.startswith("_"):
            continue
        if getattr(obj, "__module__", None) != path:
            continue
        if inspect.isclass(obj) or inspect.isfunction(obj):
            members.append((name, obj))
    for name, obj in members:
        if inspect.isclass(obj):
            lines += [f"## class `{name}{_signature(obj)}`", ""]
            if obj.__doc__:
                lines += [_first_para(obj.__doc__), ""]
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") or not callable(meth):
                    continue
                doc = inspect.getdoc(meth)
                lines += [f"### `{name}.{mname}{_signature(meth)}`", ""]
                if doc:
                    lines += [_first_para(doc), ""]
        else:
            lines += [f"## `{name}{_signature(obj)}`", ""]
            doc = inspect.getdoc(obj)
            if doc:
                lines += [_first_para(doc), ""]
    return "\n".join(lines) + "\n"


def _index() -> str:
    lines = [
        "# moolib_tpu — API documentation",
        "",
        "A TPU-native distributed-RL framework with the capability surface "
        "of moolib. Generated by `tools/gen_api_docs.py` from the live "
        "docstrings; regenerate after changing public APIs.",
        "",
        "| module | role |",
        "|---|---|",
    ]
    for path, role in MODULES:
        fname = path.replace(".", "_") + ".md"
        lines.append(f"| [`{path}`](api/{fname}) | {role} |")
    lines += [
        "",
        "Architecture overview: [design.md](design.md). Lint rules, "
        "suppression syntax, and the baseline workflow: "
        "[analysis.md](analysis.md). Fault model, delivery guarantees, "
        "and seed replay: [reliability.md](reliability.md). Metric name "
        "catalogue, span semantics, and the scrape how-to: "
        "[observability.md](observability.md). Black-box flight "
        "recorder, incident bundles, clock-aligned cross-peer "
        "post-mortems: [incidents.md](incidents.md). Benchmark harness "
        "protocol, CPU-proxy suite, perf budgets, and the "
        "trend/regression gate: [perf.md](perf.md). Serving-tier "
        "architecture, failure model, deadline/shedding semantics, and "
        "retry-safety rules: [serving.md](serving.md). Fleet tier — "
        "declarative cohort specs, supervised roles, epoch-fenced "
        "controller failover, and SLO-gated canary rollouts: "
        "[fleet.md](fleet.md).",
        "",
        "Other entry points:",
        "",
        "- `benchmark/run.py` — the benchmark: device speed of every "
        "cell of `BENCHMARK.json`, on the chip (see `PERF.md`).",
        "- `tools/perf.py` — perfwatch CLI: host-plane CPU-proxy perf "
        "suite + budgets + trend gate (CI stage).",
        "- `tools/moolint.py` — static-analysis CLI; `tools/ci_check.sh` — "
        "lint + tier-1 tests, one entrypoint.",
        "- `tools/chaos_soak.py` — chaosnet scenario runner "
        "(`--smoke` CI stage, `--seed N --minutes M` soak).",
        "- `tools/serving_load.py` — serving-tier load generator "
        "(throughput/latency report, optional mid-run replica kill).",
        "- `tools/telemetry_dump.py` — scrape a live cohort's "
        "`__telemetry` endpoints into one merged metrics/trace dump "
        "(`--bundle`: incident-bundle format).",
        "- `tools/incident_report.py` — crawl `__flightrec` across a "
        "live cohort into one clock-aligned incident timeline "
        "(`--smoke` CI stage, `--bundles` offline merge).",
        "- `tools/telemetry_smoke.py` — live scrape validation + "
        "disabled-mode overhead budget (CI stage).",
        "- `python -m moolib_tpu.broker` — standalone membership broker.",
        "",
    ]
    return "\n".join(lines)


def generate() -> dict:
    out = {os.path.join(DOCS, "index.md"): _index()}
    for path, role in MODULES:
        fname = path.replace(".", "_") + ".md"
        out[os.path.join(DOCS, "api", fname)] = _doc_module(path, role)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="verify committed docs match the code")
    args = ap.parse_args()
    files = generate()
    stale = []
    for fpath, content in files.items():
        if args.check:
            try:
                with open(fpath) as f:
                    if f.read() != content:
                        stale.append(fpath)
            except FileNotFoundError:
                stale.append(fpath)
        else:
            os.makedirs(os.path.dirname(fpath), exist_ok=True)
            with open(fpath, "w") as f:
                f.write(content)
    if args.check:
        if stale:
            print("STALE docs (rerun tools/gen_api_docs.py):")
            for s in stale:
                print(f"  {os.path.relpath(s, ROOT)}")
            sys.exit(1)
        print(f"docs up to date ({len(files)} files)")
    else:
        print(f"wrote {len(files)} files under docs/")


if __name__ == "__main__":
    main()
