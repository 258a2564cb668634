"""Attention backend benchmark: dense vs blockwise vs flash (pallas) vs
zigzag-ring, as steps/s + attention MFU at long context.

This tool:

1. validates flash fwd+bwd as compiled by Mosaic against the dense oracle
   — numerics asserted, result recorded (``chip_smoke.py`` is the gate for
   this; here it labels the timing rows);
2. times a training-shaped step (attention + sum-of-squares loss backward)
   per backend at T in {2048, 8192}, recording steps/s and achieved
   attention TFLOP/s vs the chip peak.

The flash rows need a TPU: the kernels are never interpreted outside the
tests, so on another backend they are recorded as errors.

Usage: python tools/attn_bench.py [--json ATTN_r04.json] [--quick]

Per-(backend, T) rows also land as perfwatch harness rows when
MOOLIB_TRENDS names a trend store. See docs/perf.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _time_grad_step(grad_fn, q, k, v, iters=5):
    """Chained honest timing via utils/benchmark.time_chained: iteration
    i+1's q depends on iteration i's dq (a negligible 1e-30-scaled nudge
    keeps the data dependency real without changing the numerics), so the
    runtime cannot pipeline or elide dispatches — the same protocol every
    other steps/s artifact in this repo uses."""
    from moolib_tpu.utils.benchmark import time_chained

    def step(c):
        q, k, v = c
        dq, _dk, _dv = grad_fn(q, k, v)
        return (q + (dq * 1e-30).astype(q.dtype), k, v)

    _, dt, _compile_s = time_chained(step, (q, k, v), iters=iters)
    return dt / iters


def attention_flops(B, H, T, D, causal=True):
    """Model FLOPs for one attention forward: QK^T + PV, 2 MACs each;
    causal halves the realized score work. Train step = 3.5x fwd (bwd
    recomputes + two matmul-shaped products per einsum)."""
    full = 2 * 2 * B * H * T * T * D
    return full // 2 if causal else full


def bench_backend(backend, B, H, T, D, dtype, iters, mesh=None):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu.ops import attention as attn_mod

    rng = np.random.default_rng(0)

    def mk(shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)

    q, k, v = (mk((B, H, T, D)) for _ in range(3))

    if backend == "zigzag":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from moolib_tpu.ops.ring_attention import (
            zigzag_order, zigzag_ring_attention,
        )

        # The zigzag layout must match the SP axis size, not the total
        # device count (dp shards don't participate in the ring).
        n = mesh.shape["sp"]
        order = zigzag_order(n, T)
        qz, kz, vz = (x[:, :, order, :] for x in (q, k, v))
        spec = NamedSharding(mesh, P(None, None, "sp", None))
        qz, kz, vz = (jax.device_put(x, spec) for x in (qz, kz, vz))

        def grad_fn(q, k, v):
            def loss(q, k, v):
                o = jax.shard_map(
                    lambda q, k, v: zigzag_ring_attention(
                        q, k, v, axis_name="sp"
                    ),
                    mesh=mesh,
                    in_specs=(P(None, None, "sp", None),) * 3,
                    out_specs=P(None, None, "sp", None),
                )(q, k, v)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        return _time_grad_step(grad_fn, qz, kz, vz, iters=iters)

    fns = {
        "dense": lambda q, k, v: attn_mod.dense_attention(
            q, k, v, causal=True
        ),
        "blockwise": lambda q, k, v: attn_mod.blockwise_attention(
            q, k, v, causal=True
        ),
        "flash": lambda q, k, v: attn_mod.flash_attention(
            q, k, v, causal=True
        ),
    }
    inner = fns[backend]

    def grad_fn(q, k, v):
        def loss(q, k, v):
            o = inner(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return _time_grad_step(grad_fn, q, k, v, iters=iters)


def validate_flash_nonintepreted(dtype):
    """Flash fwd+bwd, compiled, vs the dense oracle; returns (ok,
    max_err_fwd, max_err_bwd, error_string)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu.ops import attention as attn_mod

    rng = np.random.default_rng(1)
    B, H, T, D = 2, 2, 512, 64
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, T, D)) * 0.2, dtype)
        for _ in range(3)
    )
    try:
        def f_loss(q, k, v):
            o = attn_mod.flash_attention(
                q, k, v, causal=True, block_q=256, block_k=256,
            )
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (fl, fo), fg = jax.value_and_grad(
            f_loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)

        def d_loss(q, k, v):
            o = attn_mod.dense_attention(q, k, v, causal=True)
            return jnp.sum(o.astype(jnp.float32) ** 2), o

        (dl, do), dg = jax.value_and_grad(
            d_loss, argnums=(0, 1, 2), has_aux=True
        )(q, k, v)
        err_fwd = float(
            jnp.max(jnp.abs(fo.astype(jnp.float32) - do.astype(jnp.float32)))
        )
        err_bwd = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                  b.astype(jnp.float32))))
            for a, b in zip(fg, dg)
        )
        tol = 0.05 if dtype == jnp.bfloat16 else 2e-2
        ok = err_fwd < tol and err_bwd < 1.0  # grads scale with T
        return ok, err_fwd, err_bwd, None
    except Exception as e:  # Mosaic rejection surfaces here
        return False, None, None, f"{type(e).__name__}: {e}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="small shapes / fewer iters (smoke)")
    ap.add_argument("--budget", type=float, default=600.0,
                    help="soft wall-clock budget in seconds")
    ap.add_argument("--round", type=int, default=5,
                    help="round number stamped into the artifact")
    args = ap.parse_args()

    from moolib_tpu.bench.harness import append_device_trend

    import jax
    import jax.numpy as jnp

    from moolib_tpu.parallel.mesh import make_mesh
    from moolib_tpu.utils.flops import device_peak_flops

    dev = jax.devices()[0]
    platform = dev.platform
    dtype = jnp.bfloat16 if platform == "tpu" else jnp.float32
    peak = device_peak_flops(dev.device_kind) if platform == "tpu" else None

    t_start = time.monotonic()
    ok, ef, eb, err = validate_flash_nonintepreted(dtype)
    art = {
        "round": args.round,
        "cmd": "python tools/attn_bench.py",
        "platform": platform,
        "device_kind": dev.device_kind,
        "dtype": str(jnp.dtype(dtype)),
        "flash_noninterpret_validation": {
            "ok": ok, "max_err_fwd": ef, "max_err_bwd": eb, "error": err,
            "note": "Mosaic acceptance + numerics vs dense oracle",
        },
        "rows": [],
    }

    B, H, D = (1, 4, 64) if args.quick else (1, 8, 128)
    iters = 2 if args.quick else 5
    Ts = (512,) if args.quick else (2048, 8192)
    n_dev = len(jax.devices())
    sp = min(4, n_dev)
    mesh = make_mesh(dp=n_dev // sp, sp=sp) if sp > 1 else None

    for T in Ts:
        for backend in ("dense", "blockwise", "flash", "zigzag"):
            if time.monotonic() - t_start > args.budget:
                art["rows"].append({"note": "budget exhausted", "T": T})
                break
            if backend == "zigzag" and mesh is None:
                continue
            if backend == "dense" and T > 4096:
                continue  # O(T^2) materialized scores: OOM risk, skip
            try:
                dt = bench_backend(
                    backend, B, H, T, D, dtype, iters, mesh=mesh
                )
                fl = 3.5 * attention_flops(B, H, T, D)  # fwd+bwd
                row = {
                    "backend": backend, "T": T, "B": B, "H": H, "D": D,
                    "ms_per_step": round(dt * 1e3, 2),
                    "steps_per_sec": round(1.0 / dt, 2),
                    "attn_tflops": round(fl / dt / 1e12, 3),
                }
                if peak:
                    row["attn_mfu"] = round(fl / dt / peak, 4)
                art["rows"].append(row)
                print(json.dumps(row), flush=True)
                append_device_trend(
                    f"attn_{backend}_T{T}_steps_per_sec",
                    row["steps_per_sec"], "steps/s",
                    "python tools/attn_bench.py",
                    extra={"backend": backend, "T": T,
                           "attn_tflops": row["attn_tflops"]},
                )
            except Exception as e:
                art["rows"].append({
                    "backend": backend, "T": T,
                    "error": f"{type(e).__name__}: {e}"[:300],
                })

    # Headline comparison: flash vs blockwise at the longest measured T.
    flash = [r for r in art["rows"]
             if r.get("backend") == "flash" and "ms_per_step" in r]
    blockw = [r for r in art["rows"]
              if r.get("backend") == "blockwise" and "ms_per_step" in r]
    if flash and blockw:
        t_common = max(
            set(r["T"] for r in flash) & set(r["T"] for r in blockw),
            default=None,
        )
        if t_common:
            f = next(r for r in flash if r["T"] == t_common)
            b = next(r for r in blockw if r["T"] == t_common)
            art["flash_vs_blockwise"] = {
                "T": t_common,
                "speedup": round(
                    b["ms_per_step"] / f["ms_per_step"], 2
                ),
            }
    print(json.dumps({k: v for k, v in art.items() if k != "rows"}))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(art, f, indent=1)


if __name__ == "__main__":
    main()
