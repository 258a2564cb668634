#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, and as the last line of standard output one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything that belongs to one thing sits in a file of its own, found by
the name the manifest gives it: ``configs/<configuration>.json``,
``reference/<configuration>.py``, ``workloads/<cell>.json``,
``drivers/<driver>.py``, ``metrics/<metric>.py``. Adding a cell, a
configuration, a kind of traffic or a per-layer metric adds files and
manifest entries and edits none.

It refuses, with a non-zero exit and no result line, to run on another
platform than the cell's file states (``tpu`` unless it says otherwise: the
rehearsal cells that live with the tests say ``cpu``) or on another number
of chips than the cell asks for.

Everything that touches jax sits under ``main``: the loop cell's EnvPool
workers are spawn-started, re-import this file, and must stay off the chip.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python can see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class Context:
    """What a driver is handed."""

    def __init__(self, args, cell, config, devices, verdict, compiles,
                 trace_dir):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.cell, self.config, self.devices = cell, config, devices
        self.verdict, self.compiles = verdict, compiles
        self.trace_dir = trace_dir

    def start_trace(self):
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans are TraceAnnotations
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)

    def stop_trace(self):
        """Stops the profiler and returns the reduced-ready trace."""
        import jax

        from benchmark.lib import xplane

        jax.profiler.stop_trace()
        return xplane.load(xplane.find_xplane(self.trace_dir))


def load_json(path):
    with open(path) as f:
        return json.load(f)


def metrics_of(manifest, kind, cell_name):
    """The manifest's metrics of ``kind`` that list this cell under
    ``workloads``, or list none (``setup_s``): those are every cell's."""
    return [
        m for m in manifest[kind]
        if cell_name in m.get("workloads", [cell_name])
    ]


def reader_path(name):
    """``metrics/<name>.py``. A quantity split by the end-to-end metric
    its cells report (``device.idle_share.learner``, ``.loop``) has one
    reader, named without the last part."""
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(HERE, "metrics", name.rpartition(".")[0] + ".py")
    return path


def load_reader(name):
    """``read(readings, context) -> value | None``."""
    path = reader_path(name)
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument(
        "--manifest", default=os.path.join(REPO, "BENCHMARK.json"),
        help="the tests' rehearsal cells have a manifest of their own",
    )
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    manifest = load_json(args.manifest)
    root = os.path.join(
        os.path.dirname(os.path.abspath(args.manifest)), manifest["paths"][0]
    )
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == args.workload), None
    )
    if entry is None:
        print(f"run.py: no cell {args.workload!r} in {args.manifest}",
              file=sys.stderr)
        return 2
    cell = load_json(os.path.join(root, "workloads", entry["name"] + ".json"))
    config_entry = next(
        c for c in manifest["configs"] if c["name"] == entry["config"]
    )
    config = load_json(os.path.join(
        os.path.dirname(os.path.abspath(args.manifest)), config_entry["file"]
    ))

    import jax

    devices = jax.devices()
    want = cell.get("platform", "tpu")
    if devices[0].platform != want or len(devices) < entry["chips"]:
        print(
            f"run.py: cell {entry['name']!r} needs {entry['chips']} x "
            f"{want}; jax found platform={devices[0].platform!r} "
            f"device_kind={devices[0].device_kind!r} count={len(devices)}. "
            "Nothing was compiled, no result.", file=sys.stderr,
        )
        return 1
    devices = devices[:entry["chips"]]

    from moolib_tpu.utils.jaxenv import enable_compile_cache

    from benchmark.lib import compare, harness

    cache_dir = enable_compile_cache()
    # Every program, however quick to build, goes to the cache: the loop's
    # helpers each compile in under jax's one-second threshold, and a run
    # that rebuilds forty of them has an unsteady set-up.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    facts = harness.device_facts(devices)
    print(f"[phases] to_devices={time.monotonic() - T0:.2f}s", flush=True)
    print(f"[run] cell={entry['name']} config={entry['config']} "
          f"seed={args.seed} seconds={args.seconds} trace={args.trace} "
          f"platform={facts['platform']} device_kind={facts['kind']!r} "
          f"count={facts['count']} compile_cache={cache_dir}", flush=True)

    ctx = Context(
        args, cell, config, devices, compare.Verdict(),
        harness.CompileLog(), os.path.join(REPO, ".bench_trace"),
    )
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    out = driver.run(ctx)

    setup_s = out["window_start"] - T0
    print(f"[setup] {setup_s:.2f} s from process start to the window's "
          "first step", flush=True)
    # Read by the driver when the window closed, before the reference ran.
    facts["memory_peak_bytes"] = out["memory_peak_bytes"]
    metrics, breakdown = {}, None
    if not ctx.trace:
        values = dict(out["end_to_end"], setup_s=setup_s)
        for m in metrics_of(manifest, "end_to_end", entry["name"]):
            metrics[m["name"]] = (values[m["name"]], m["unit"])
    else:
        from benchmark.lib import xplane

        readings = dict(out["readings"])
        trace = readings.get("trace")
        summary = xplane.summarize(trace) if trace is not None else None
        readings["summary"] = summary
        reader_ctx = {
            "config": config, "cell": cell, "device": facts,
            "chips": len(devices), "devices": devices,
        }
        for m in metrics_of(manifest, "per_layer", entry["name"]):
            value = load_reader(m["name"])(readings, reader_ctx)
            if value is not None:  # nothing to read: left out of the line
                metrics[m["name"]] = (value, m["unit"])
        if summary is not None:
            facts["busy_s"] = summary["busy_s"]
            facts["window_s"] = summary["window_s"]
            unowned = readings.get("unowned_gap", "(none)")
            breakdown = {
                "device_ops": summary["device_ops"],
                "idle_gaps": [
                    [unowned if name == "(none)" else name, seconds]
                    for name, seconds in summary["idle_gaps"]
                ],
            }
    print(harness.result_line(
        ctx.verdict.correct, out["attempted"], out["failed"], metrics, facts,
        breakdown,
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
