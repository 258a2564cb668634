"""Headline benchmark: IMPALA learner throughput in env-steps/sec/chip.

Runs the full jitted IMPALA training step (deep ResNet forward on Atari-shaped
pixel rollouts, V-trace targets, backward, optimizer update) on the available
chip(s) and reports consumed env frames per second per chip.

Baseline context (BASELINE.md): the reference publishes no numeric throughput
table; the driver's north-star is 1M env-steps/sec across a TPU v4-32
(32 cores), i.e. 31,250 env-steps/sec/core. ``vs_baseline`` is measured
throughput relative to that per-chip north-star share.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"} — the
contract the external bench driver's BENCH_r{NN}.json collector expects.
Since PR 7 this is a thin wrapper over the perfwatch harness
(moolib_tpu/bench/): the same run also lands a full harness-schema row in
the trend store when MOOLIB_TRENDS names one. See docs/perf.md.
"""

from __future__ import annotations

import json
import os
import sys

NORTH_STAR_PER_CHIP = 1_000_000 / 32  # env-steps/sec/chip share


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu.learner import (
        ImpalaConfig,
        make_impala_train_step,
        make_train_state,
        replicate_state,
    )
    from moolib_tpu.models import ImpalaNet
    from moolib_tpu.parallel.mesh import make_mesh, shard_batch
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    n_chips = len(devices)

    # Unroll/frame shape mirrors the reference's vtrace example defaults
    # (reference: examples/vtrace/config.yaml — unroll_length 20, Atari
    # 84x84x4); B=256/chip saturates the MXU better than the per-peer 32
    # (measured 80k vs 45k env-steps/s/chip on one v5e with honest
    # readback timing).
    # MOOLIB_BENCH_BATCH overrides per-chip B for smoke runs on slow backends.
    per_chip_b = int(os.environ.get("MOOLIB_BENCH_BATCH", 256))
    T, B, H, W, C, A = 20, per_chip_b * n_chips, 84, 84, 4, 6
    net = ImpalaNet(
        num_actions=A, use_lstm=False, compute_dtype=jnp.bfloat16
    )
    rng = np.random.default_rng(0)
    batch = {
        "obs": jnp.asarray(
            rng.integers(0, 255, (T + 1, B, H, W, C), dtype=np.uint8)
        ),
        "done": jnp.asarray(rng.random((T + 1, B)) < 0.02),
        "rewards": jnp.asarray(rng.standard_normal((T + 1, B)), jnp.float32),
        "actions": jnp.asarray(rng.integers(0, A, (T, B)), jnp.int32),
        "behavior_logits": jnp.zeros((T, B, A), jnp.float32),
        "core_state": (),
    }
    params = net.init(
        jax.random.PRNGKey(0), batch["obs"][:, :1], batch["done"][:, :1], ()
    )
    opt = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(6e-4))
    state = make_train_state(params, opt)
    if n_chips > 1:
        # Multi-chip: dp-shard the batch over the mesh so per-chip
        # throughput is honest (the metric divides by n_chips).
        mesh = make_mesh(dp=n_chips, devices=devices)
        step = make_impala_train_step(
            net.apply, opt, ImpalaConfig(), mesh=mesh, donate=True
        )
        state = replicate_state(state, mesh)
        batch = shard_batch(mesh, batch)
    else:
        step = make_impala_train_step(
            net.apply, opt, ImpalaConfig(), donate=True
        )
    # Honest timing protocol (chained in-jit steps + D2H fingerprint
    # readback) — shared single source: moolib_tpu/utils/benchmark.py.
    from moolib_tpu.utils.benchmark import time_train_step

    # MOOLIB_BENCH_ITERS shrinks the chained-iteration count for smoke
    # runs on slow backends.
    iters = int(os.environ.get("MOOLIB_BENCH_ITERS", 10))
    # MOOLIB_BENCH_PROFILE=<dir> captures an XLA trace of the timed run
    # only (never the compile, which would drown the timeline).
    state, dt, _compile_s = time_train_step(
        step, state, batch, iters=iters,
        trace_dir=os.environ.get("MOOLIB_BENCH_PROFILE"),
    )

    steps_per_sec = iters * T * B / dt
    per_chip = steps_per_sec / max(1, n_chips)

    # MFU: analytic model FLOPs (forward x3 for the backward; convs dominate
    # ImpalaNet — see moolib_tpu/utils/flops.py) over the chip's peak bf16
    # throughput. The actionable tuning number: how busy is the MXU.
    from moolib_tpu.utils.flops import device_peak_flops, impala_train_flops

    flops_per_step = impala_train_flops((T + 1) * B, num_actions=A)
    achieved = flops_per_step * iters / dt / max(1, n_chips)
    peak = device_peak_flops(devices[0].device_kind)
    legacy = {
        "metric": "impala_train_env_steps_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "env-steps/s/chip",
        "vs_baseline": round(per_chip / NORTH_STAR_PER_CHIP, 3),
        "mfu": round(achieved / peak, 4),
        "model_tflops_per_sec_per_chip": round(achieved / 1e12, 2),
        "device_kind": devices[0].device_kind,
    }
    print(json.dumps(legacy))

    # Harness-schema row into the trend store (no-op unless MOOLIB_TRENDS
    # is set): the same number, full provenance, device-suite series.
    from moolib_tpu.bench.harness import append_device_trend

    append_device_trend(
        legacy["metric"], per_chip, legacy["unit"], "python bench.py",
        stats={"n": 1, "timed_s": dt, "iters": iters,
               "frames_per_iter": T * B},
        extra={k: v for k, v in legacy.items()
               if k not in ("metric", "value", "unit")},
    )


if __name__ == "__main__":
    sys.exit(main())
