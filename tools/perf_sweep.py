"""Perf sweep for the IMPALA learner bench: vary batch size / dtypes and
report env-steps/s/chip + MFU for each config under the honest timing
protocol from bench.py (chained in-jit steps, D2H scalar readback).

Usage: python tools/perf_sweep.py [config ...]
Configs are "B=512,dtype=bf16" style key=value strings; no args runs the
default grid. One JSON line per config (unchanged contract); each
successful config also lands a perfwatch harness row — one trend series
per config string — when MOOLIB_TRENDS names a store. See docs/perf.md.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def run_config(
    B: int, dtype: str, s2d: int = 1, iters: int = None, mxu: int = 0
) -> dict:
    if iters is None:
        iters = int(os.environ.get("MOOLIB_BENCH_ITERS", 10))
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu.learner import ImpalaConfig, make_impala_train_step, make_train_state
    from moolib_tpu.models import ImpalaNet
    from moolib_tpu.utils.flops import device_peak_flops, impala_train_flops

    T, H, W, C, A = 20, 84, 84, 4, 6
    cdt = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype]
    # mxu=1: the labeled MXU-friendly variant (VERDICT r4 #3) — model-
    # internal space-to-depth(2) + conv channels padded to 128 lanes.
    # Function-preserving w.r.t. channel padding (models/impala.py
    # widen_impala_params parity test); a DIFFERENT torso geometry from the
    # headline architecture, reported as such.
    pad_to = 128 if mxu else 0
    net = ImpalaNet(
        num_actions=A, use_lstm=False, compute_dtype=cdt,
        space_to_depth_factor=2 if mxu else 1, channel_pad_to=pad_to,
    )
    rng = np.random.default_rng(0)
    obs = rng.integers(0, 255, (T + 1, B, H, W, C), dtype=np.uint8)
    h, w, c = H, W, C
    if s2d > 1:
        # One canonical s2d (the parity-pinned block ordering lives with
        # the model): trades spatial resolution for channel depth — the
        # tile-efficiency lever PERF_ANALYSIS.md names. A LABELED variant.
        # Pure reshape/transpose, so it runs directly on the host numpy
        # array — no device round-trip before the benchmark's own H2D.
        from moolib_tpu.models import space_to_depth

        obs = space_to_depth(obs, s2d)
        h, w, c = H // s2d, W // s2d, C * s2d * s2d
    if net.space_to_depth_factor > 1:
        # Model-internal s2d: FLOPs accounting follows the variant's real
        # geometry, read from the net's own fields (not re-stated here).
        f = net.space_to_depth_factor
        h, w, c = h // f, w // f, c * f * f
    batch = {
        "obs": jnp.asarray(obs),
        "done": jnp.asarray(rng.random((T + 1, B)) < 0.02),
        "rewards": jnp.asarray(rng.standard_normal((T + 1, B)), jnp.float32),
        "actions": jnp.asarray(rng.integers(0, A, (T, B)), jnp.int32),
        "behavior_logits": jnp.zeros((T, B, A), jnp.float32),
        "core_state": (),
    }
    params = net.init(jax.random.PRNGKey(0), batch["obs"][:, :1], batch["done"][:, :1], ())
    opt = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(6e-4))
    state = make_train_state(params, opt)
    step = make_impala_train_step(net.apply, opt, ImpalaConfig(), donate=True)

    from moolib_tpu.utils.benchmark import time_train_step

    state, dt, compile_s = time_train_step(step, state, batch, iters=iters)

    steps_per_sec = iters * T * B / dt
    # The model's own padding rule applied to the model's own channel
    # tuple, so the FLOPs denominators cannot drift from what actually ran.
    from moolib_tpu.models.impala import _pad_up

    chans = tuple(_pad_up(ch, net.channel_pad_to) for ch in net.channels)
    flops_step = impala_train_flops(
        (T + 1) * B, height=h, width=w, in_channels=c, num_actions=A,
        channels=chans,
    )
    achieved = flops_step * iters / dt
    peak = device_peak_flops(jax.devices()[0].device_kind)
    return {
        "B": B,
        "dtype": dtype,
        "s2d": s2d,
        "mxu": mxu,
        "env_steps_per_sec": round(steps_per_sec, 1),
        "tflops": round(achieved / 1e12, 2),
        "mfu": round(achieved / peak, 4),
        "compile_s": round(compile_s, 1),
        "timed_s": round(dt, 3),
        "note": (
            "MXU-friendly variant (s2d=2 + channels padded to 128): "
            "different torso geometry, NOT the headline architecture"
            if mxu else
            "space-to-depth variant: different torso geometry, "
            "NOT the headline architecture" if s2d > 1 else None
        ),
    }


def main():
    grid = [
        (256, "bf16", 1), (512, "bf16", 1), (1024, "bf16", 1),
        (256, "f32", 1), (256, "bf16", 2),
    ]
    if len(sys.argv) > 1:
        grid = []
        for arg in sys.argv[1:]:
            kv = dict(p.split("=") for p in arg.split(","))
            grid.append((int(kv.get("B", 256)), kv.get("dtype", "bf16"),
                         int(kv.get("s2d", 1)), int(kv.get("mxu", 0))))
    from moolib_tpu.bench.harness import append_device_trend

    for cfg in grid:
        B, dtype, s2d = cfg[0], cfg[1], cfg[2]
        mxu = cfg[3] if len(cfg) > 3 else 0
        row = run_config(B, dtype, s2d, mxu=mxu)
        print(json.dumps(row), flush=True)
        cfg_id = f"B{B}_{dtype}_s2d{s2d}_mxu{mxu}"
        append_device_trend(
            f"sweep_{cfg_id}_env_steps_per_sec",
            row["env_steps_per_sec"], "env-steps/s",
            f"python tools/perf_sweep.py "
            f"B={B},dtype={dtype},s2d={s2d},mxu={mxu}",
            stats={"n": 1, "timed_s": row["timed_s"],
                   "compile_s": row["compile_s"]},
            extra={k: row[k] for k in ("tflops", "mfu") if k in row},
        )


if __name__ == "__main__":
    main()
