"""End-to-end benchmark: the FULL IMPALA loop — EnvPool acting, two-stage
batching, H2D staging, jitted act + train steps, Accumulator-driven updates
— on synthetic Atari-shaped pixels (no ALE dependency, deterministic env
cost), measured as env-steps/s.

This is the number the north-star metric actually names (BASELINE.md: env
steps consumed end to end), next to bench.py's learner-only ceiling. The
gap between the two is the host-side pipeline cost: env stepping, batching,
H2D, and RPC control — everything the learner-only bench excludes.

Prints ONE JSON line:
  {"metric": "impala_e2e_env_steps_per_sec", "value", "unit",
   "learner_only_gap_note"}
(the unchanged collector contract). Since PR 7 the run also lands a
perfwatch harness row in the trend store when MOOLIB_TRENDS names one.
See docs/perf.md.
"""

from __future__ import annotations

import json
import sys
import time


def main(duration: float = 60.0) -> None:
    from moolib_tpu.examples.vtrace.experiment import VtraceConfig, train
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()

    import os as _os

    rows = []
    cfg = VtraceConfig(
        env="synthetic",
        actor_batch_size=64,
        learn_batch_size=64,
        virtual_batch_size=64,
        # More env workers than cores just thrash the scheduler (this
        # build host has ONE core; the workers and the learner time-slice
        # it either way). Must divide actor_batch_size (EnvPool slices
        # envs evenly), so pick the largest power-of-two divisor <= cores.
        num_actor_processes=max(
            w for w in (1, 2, 4) if w <= (_os.cpu_count() or 1) or w == 1
        ),
        num_actor_batches=2,
        unroll_length=20,
        total_steps=10**9,  # bounded by max_seconds below
        log_interval_steps=2_000,
        stats_interval=2.0,
        max_seconds=duration,
    )
    t0 = time.perf_counter()
    rows = train(cfg, log_fn=lambda *_a, **_k: None)
    elapsed = time.perf_counter() - t0
    total_steps = rows[-1]["env_steps"] if rows else 0
    # Skip the warmup window (compile + pool spin-up): measure from the
    # first logged row to the last (rows carry a monotonic 'time' stamp).
    if len(rows) >= 2:
        steps = rows[-1]["env_steps"] - rows[0]["env_steps"]
        span = rows[-1]["time"] - rows[0]["time"]
        sps = steps / max(span, 1e-9)
    else:
        sps = total_steps / elapsed
    legacy = {
        "metric": "impala_e2e_env_steps_per_sec",
        "value": round(sps, 1),
        "unit": "env-steps/s (1 peer, acting+batching+H2D+train)",
        "total_env_steps": int(total_steps),
        "wall_s": round(elapsed, 1),
        "learner_only_gap_note": (
            "bench.py measures the resident-batch train step alone; "
            "the difference to this number is host pipeline cost "
            "(env stepping, batching, H2D, RPC control)"
        ),
    }
    print(json.dumps(legacy))

    from moolib_tpu.bench.harness import append_device_trend

    append_device_trend(
        legacy["metric"], sps, "env-steps/s",
        f"python bench_e2e.py {duration:g}",
        stats={"n": 1, "wall_s": elapsed,
               "total_env_steps": int(total_steps)},
    )


if __name__ == "__main__":
    dur = float(sys.argv[1]) if len(sys.argv) > 1 else 60.0
    sys.exit(main(dur))
