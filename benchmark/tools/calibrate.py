#!/usr/bin/env python3
"""Read the numbers a cell's limits are set from, on the chip, in one
process: for each seed the program's first steps against the reference, and
for the control seeds the reference at the nearest lower precision in the
program's place. Prints one JSON line per seed and a summary: the largest
each number takes over the sound runs, the smallest over the control's.

    python3 benchmark/tools/calibrate.py --workload atari_learner \
        --seeds 12 --control-seeds 3 [--first-seed 1000]

Not part of a benchmark run: ``run.py`` never calls it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_147_480_000)
    p.add_argument("--manifest",
                   default=os.path.join(REPO, "BENCHMARK.json"))
    args = p.parse_args()
    sys.path.insert(0, REPO)
    sys.path.insert(0, os.path.dirname(HERE))
    import run as bench_run

    manifest = bench_run.load_json(args.manifest)
    base = os.path.dirname(os.path.abspath(args.manifest))
    entry = next(w for w in manifest["workloads"]
                 if w["name"] == args.workload)
    cell = bench_run.load_json(os.path.join(
        base, manifest["paths"][0], "workloads", entry["name"] + ".json"))
    config = bench_run.load_json(os.path.join(base, next(
        c["file"] for c in manifest["configs"]
        if c["name"] == entry["config"])))

    import jax

    from moolib_tpu.utils.jaxenv import enable_compile_cache

    from benchmark.lib import harness, reference_train

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()[:entry["chips"]]
    print(f"[calibrate] {harness.device_facts(devices)}", flush=True)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    control = reference_train.CONTROL_OF[config["precision"]]
    sound, controls = [], []
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    handle = driver.calibration(cell, config, devices)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        numbers = handle.sound(seed)
        sound.append(numbers)
        print(json.dumps({"seed": seed, "side": "program", **numbers,
                          "seconds": time.perf_counter() - t0}), flush=True)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            numbers = handle.control(seed, control)
            controls.append(numbers)
            print(json.dumps({"seed": seed, "side": f"control:{control}",
                              **numbers,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    print(f"[calibrate] memory stats {harness.memory_stats(devices)}",
          flush=True)
    summary = {}
    for name in sound[0]:
        summary[name] = {
            "sound_largest": max(s[name] for s in sound),
            "control_smallest": (
                min(c[name] for c in controls if name in c)
                if controls and name in controls[0] else None
            ),
        }
    print("[calibrate] " + json.dumps({
        "workload": args.workload, "seeds": len(sound),
        "control_seeds": len(controls), "control": control, **summary,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
