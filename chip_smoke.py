#!/usr/bin/env python
"""chip_smoke.py: does the system still start on the chip?

One process, every local TPU device, the normal entry points, full width:

1. ``learner``  the jitted IMPALA learner (``make_impala_train_step``,
   donating) on ImpalaNet 16/32/32, 84x84x4 uint8, bf16, T=20, B=256 per
   chip: a handful of steps, finite loss every step, ``state.step``
   advancing, nothing compiled after the first step, state resident on
   every chip and the batch split over all of them;
2. ``dp_parity`` (more than one chip) the first step's loss and grad_norm on
   all chips against one chip on the same global batch;
3. ``trainer``  ``examples/vtrace/experiment.py:train`` at the shipped
   ``config.yaml`` shapes: spawn-started EnvPool workers, Batcher,
   in-process broker, Accumulator, act / grad / apply steps, until at least
   three updates have been applied — with the workers shown to have stayed
   off the chip and the state shown to live on every chip;
4. ``flash``  the two Pallas flash-attention kernels as compiled by Mosaic
   against ``dense_attention`` at the default (256, 256) blocks;
5. ``auto_backend``  what ``attention(backend="auto")`` resolves to inside
   the jitted learner step of a TransformerNet at the shipped unroll
   length, checked against the lowered program, plus one step of it.

Weights are random from a seed, depth is the models' own. Exits non-zero —
printing no result line — if no TPU is found (before compiling anything),
if any phase raises, yields a non-finite value or ran somewhere other than
it names, or if the native extension did not build and load. There is no
``try/except`` around a phase. A passing run ends with a ``[summary]`` line
(wall and per-phase compile seconds, ``"claim": null``) and then, last on
stdout, the result: one JSON object with exactly these keys,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

Everything that touches jax sits under :func:`main`: EnvPool workers are
spawn-started and re-import this file, and a second process cannot open a
chip the parent holds.
"""

from __future__ import annotations

import dataclasses
import json
import multiprocessing
import os
import sys
import time

LEARNER_STEPS = 5
TRAINER_MIN_UPDATES = 3

# The flash kernels and the float32 reference differ by what the MXU does to
# float32 operands at default precision: it rounds them to bfloat16 (2^-9
# relative), under Mosaic and under XLA alike (on a v5e, dense_attention at
# default precision is itself 1.0e-2 away from dense_attention at
# "highest"). Two chained matmuls through an exp leave outputs and
# gradients within ~1% of the reference's largest value (measured 0.2-0.6%,
# PR 21 chip run); a structural error — a wrong mask, a dropped block — is
# off by O(100%). Bound: 2% of max|reference|.
FLASH_TOL = 2e-2
# Data-parallel parity: the model computes in bfloat16 (8 significant bits,
# 2^-8 = 0.4% per rounding) and a 64-row per-chip batch may tile and reduce
# in another order than a 256-row one, so bitwise equality is not expected;
# loss and grad_norm are float32 means over 5k frames, which average the
# rounding down (measured 2e-4 on four v5e chips, PR 21). Bound: 1% relative.
DP_TOL = 1e-2

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    """A check on what came out of a phase did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def result_line(device: dict) -> str:
    """The line a passing run ends its stdout with: ``ok`` and ``device``
    (platform, kind, count as jax reports them) and no other key —
    whoever runs the smoke parses exactly this."""
    return json.dumps({
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    })


class CompileLog:
    """Every XLA program this process builds, from jax's own monitoring
    events: (program name, seconds) per backend compile — a persistent-
    cache fetch counts, with the fetch time — plus the cache-hit count."""

    def __init__(self):
        import jax.monitoring

        self.programs = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **kw):
        if event == _BACKEND_COMPILE:
            self.programs.append((kw.get("fun_name", "?"), seconds))

    def _event(self, event, **kw):
        if event == _CACHE_HIT:
            self.cache_hits += 1

    def mark(self):
        return len(self.programs), self.cache_hits

    def since(self, mark) -> dict:
        programs = self.programs[mark[0]:]
        return {
            "programs": len(programs),
            "compile_s": round(sum(s for _, s in programs), 2),
            "cache_hits": self.cache_hits - mark[1],
        }

    def count(self, name: str, mark) -> int:
        return sum(1 for n, _ in self.programs[mark[0]:] if n == name)


def device_ids(tree) -> list:
    """Per leaf, the sorted ids of the devices holding it."""
    import jax

    return [
        sorted(d.id for d in leaf.sharding.device_set)
        for leaf in jax.tree_util.tree_leaves(tree)
    ]


def check_resident_everywhere(tree, devices, what: str) -> None:
    """Every leaf is held, whole, by every device."""
    import jax

    want = sorted(d.id for d in devices)
    for leaf in jax.tree_util.tree_leaves(tree):
        got = sorted(d.id for d in leaf.sharding.device_set)
        check(
            got == want and leaf.is_fully_replicated,
            f"{what}: a leaf of shape {leaf.shape} lives on devices {got} "
            f"(replicated={leaf.is_fully_replicated}), expected whole on "
            f"{want}",
        )


def check_split_everywhere(x, devices, what: str) -> None:
    """The array's shards sit on as many distinct devices as there are."""
    got = sorted({s.device.id for s in x.addressable_shards})
    check(
        got == sorted(d.id for d in devices),
        f"{what}: shards on devices {got}, expected one per device of "
        f"{sorted(d.id for d in devices)}",
    )
    if len(devices) > 1:
        check(
            not x.is_fully_replicated,
            f"{what}: replicated on every device instead of split",
        )


def check_platform(tree, what: str) -> None:
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        platforms = {d.platform for d in leaf.sharding.device_set}
        check(platforms == {"tpu"}, f"{what} ran on {platforms}, not tpu")


# --------------------------------------------------------------------------
# 1 + 2. the jitted learner
# --------------------------------------------------------------------------


def run_learner(devices, global_batch: int, steps: int, donate: bool,
                compiles: CompileLog) -> dict:
    """``steps`` calls of the IMPALA train step on ``devices`` (dp over all
    of them) at T=20, ``global_batch`` envs; the batch and the weights
    depend only on the seed and ``global_batch``, not on the devices."""
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

    T, B, H, W, C, A = 20, global_batch, 84, 84, 4, 6
    net = ImpalaNet(num_actions=A, use_lstm=False, compute_dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    batch = {
        "obs": rng.integers(0, 255, (T + 1, B, H, W, C), dtype=np.uint8),
        "done": rng.random((T + 1, B)) < 0.02,
        "rewards": rng.standard_normal((T + 1, B)).astype(np.float32),
        "actions": rng.integers(0, A, (T, B)).astype(np.int32),
        "behavior_logits": rng.standard_normal((T, B, A)).astype(np.float32),
        "core_state": (),
    }
    params = net.init(
        jax.random.PRNGKey(0), batch["obs"][:, :1], batch["done"][:, :1], ()
    )
    opt = optax.chain(optax.clip_by_global_norm(40.0), optax.adam(6e-4))
    state = make_train_state(params, opt)
    if len(devices) > 1:
        mesh = make_mesh(dp=len(devices), devices=devices)
        state = replicate_state(state, mesh)
        batch = shard_batch(mesh, batch)
    else:
        mesh = None
        state = jax.device_put(state, devices[0])
        batch = jax.device_put(batch, devices[0])
    step = make_impala_train_step(
        net.apply, opt, ImpalaConfig(), mesh=mesh, donate=donate
    )

    mark = compiles.mark()
    for i in range(steps):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        loss = float(metrics["total_loss"])  # waits for the step
        grad_norm = float(metrics["grad_norm"])
        if i == 0:
            first = {
                "loss": loss, "grad_norm": grad_norm,
                "first_call_s": round(time.perf_counter() - t0, 2),
                **compiles.since(mark),
            }
            steady = compiles.mark()
        check(
            np.isfinite(loss) and np.isfinite(grad_norm),
            f"learner step {i}: loss {loss}, grad_norm {grad_norm}",
        )
        check(
            int(state.step) == i + 1,
            f"learner step {i}: state.step is {int(state.step)}",
        )
    check(
        compiles.since(steady)["programs"] == 0 and step._cache_size() == 1,
        f"learner recompiled after its first step: "
        f"{compiles.programs[steady[0]:]}, jit cache {step._cache_size()}",
    )
    check_platform((state, metrics), "learner")
    check_resident_everywhere(
        (state.params, state.opt_state), devices, "learner state"
    )
    check_split_everywhere(batch["obs"], devices, "learner batch")
    return {
        "devices": len(devices), "T": T, "global_batch": B, "steps": steps,
        "first_loss": first["loss"], "first_grad_norm": first["grad_norm"],
        "last_loss": loss, "first_call_s": first["first_call_s"],
        "compile_s": first["compile_s"], "programs": first["programs"],
        "cache_hits": first["cache_hits"],
        "state_on": device_ids(state.params)[0],
        "batch_shards_on": sorted(
            s.device.id for s in batch["obs"].addressable_shards
        ),
    }


def phase_dp_parity(devices, global_batch: int, compiles: CompileLog) -> dict:
    import numpy as np

    one = run_learner(devices[:1], global_batch, 1, False, compiles)
    many = run_learner(devices, global_batch, 1, False, compiles)
    out = {
        "global_batch": global_batch, "tolerance": DP_TOL,
        "compile_s": round(one["compile_s"] + many["compile_s"], 2),
    }
    for key in ("first_loss", "first_grad_norm"):
        rel = abs(many[key] - one[key]) / abs(one[key])
        out[key] = {
            "one_chip": one[key], f"{len(devices)}_chips": many[key],
            "rel_diff": float(np.format_float_scientific(rel, 2)),
        }
        check(
            rel <= DP_TOL,
            f"dp parity: {key} {many[key]} on {len(devices)} chips vs "
            f"{one[key]} on one (rel {rel:.2e} > {DP_TOL})",
        )
    return out


# --------------------------------------------------------------------------
# 3. the trainer
# --------------------------------------------------------------------------


def _chip_handles(pid: int) -> list:
    """Evidence that process ``pid`` opened a TPU: libtpu mapped into it, or
    an accelerator device node among its open files."""
    found = []
    with open(f"/proc/{pid}/maps") as f:
        if "libtpu" in f.read():
            found.append("libtpu mapped")
    for fd in os.listdir(f"/proc/{pid}/fd"):
        try:
            target = os.readlink(f"/proc/{pid}/fd/{fd}")
        except OSError:
            continue  # closed between listdir and readlink
        if target.startswith(("/dev/accel", "/dev/vfio")):
            found.append(target)
    return found


def _watch(factory, record: dict):
    """Wrap a learner step factory so that its product remembers its calls:
    count, first-call seconds, and the newest arguments and result (to read
    placement and values from afterwards). Observation only."""

    def make(*args, **kwargs):
        fn = factory(*args, **kwargs)
        record.update(fn=fn, calls=0, kwargs=kwargs)

        def watched(*call_args):
            t0 = time.perf_counter()
            out = fn(*call_args)
            if record["calls"] == 0:
                record["first_call_s"] = round(time.perf_counter() - t0, 2)
            record["calls"] += 1
            record["args"], record["out"] = call_args, out
            if "metrics" in record:
                record["metrics"].append(out[1])
            return out

        return watched

    return make


def phase_trainer(devices, compiles: CompileLog, total_steps: int = 20_000,
                  **overrides) -> dict:
    import jax
    import numpy as np
    import yaml

    from moolib_tpu import learner
    from moolib_tpu.examples.vtrace import experiment

    with open(
        os.path.join(os.path.dirname(experiment.__file__), "config.yaml")
    ) as f:
        shipped = yaml.safe_load(f)
    # The shipped shapes, unshrunk; only how long to run and how often to
    # log are set here (enough env steps for > 3 virtual batches).
    cfg = dataclasses.replace(
        experiment.VtraceConfig(**shipped), total_steps=total_steps,
        log_interval_steps=total_steps // 10, **overrides,
    )
    check(cfg.env == "synthetic", f"config.yaml env is {cfg.env!r}")

    act, grad, apply = {}, {"metrics": []}, {}
    workers = {}

    def log_fn(line):
        print("  " + line, flush=True)
        for child in multiprocessing.active_children():
            workers[child.pid] = _chip_handles(child.pid)

    factories = ("make_act_step", "make_grad_step", "make_apply_step")
    originals = {name: getattr(learner, name) for name in factories}
    mark = compiles.mark()
    t0 = time.perf_counter()
    try:
        for name, record in zip(factories, (act, grad, apply)):
            setattr(learner, name, _watch(originals[name], record))
        rows = experiment.train(cfg, log_fn=log_fn)
    finally:
        for name, original in originals.items():
            setattr(learner, name, original)
    wall = time.perf_counter() - t0
    check(not multiprocessing.active_children(), "train() left workers behind")

    check(rows, "train() logged nothing")
    last = rows[-1]
    updates = int(last["updates"])
    check(
        updates >= TRAINER_MIN_UPDATES,
        f"trainer applied {updates} updates in {last['env_steps']} env "
        f"steps, wanted >= {TRAINER_MIN_UPDATES} (skips {last['skips']}, "
        f"dropped_unrolls {last['dropped_unrolls']})",
    )
    check(apply["calls"] == updates, f"{apply['calls']} applies vs {updates}")
    check(apply["kwargs"].get("donate") is True, "apply step does not donate")
    losses = [float(m["total_loss"]) for m in grad["metrics"]]
    check(
        losses and np.all(np.isfinite(losses)),
        f"trainer loss not finite: {losses}",
    )

    state = apply["out"]
    check(int(state.step) == updates, f"state.step {int(state.step)}")
    check(
        all(
            bool(np.isfinite(np.asarray(leaf, np.float32)).all())
            for leaf in jax.tree_util.tree_leaves(state.params)
        ),
        "trainer params not finite after the updates",
    )
    check(
        cfg.learn_batch_size % len(devices) == 0,
        "learn_batch_size does not split over the chips",
    )
    check_platform((state, grad["out"], act["out"]), "trainer")
    check_resident_everywhere(
        (state.params, state.opt_state), devices, "trainer state"
    )
    batch = grad["args"][1]
    check_split_everywhere(batch["obs"], devices, "trainer learn batch")
    check(
        tuple(batch["obs"].shape)
        == (cfg.unroll_length + 1, cfg.learn_batch_size, 84, 84, 4),
        f"learn batch obs shape {batch['obs'].shape}",
    )

    # One program per step, however many calls: "jit(<function name>)".
    programs = {}
    for record in (act, grad, apply):
        jitted = getattr(record["fn"], "__wrapped__", record["fn"])
        name = f"jit({jitted.__name__})"
        programs[name] = compiles.count(name, mark)
        check(
            programs[name] == 1 and jitted._cache_size() == 1,
            f"trainer built {name} {programs[name]} times "
            f"(jit cache {jitted._cache_size()})",
        )

    check(
        len(workers) >= cfg.num_actor_processes,
        f"saw {len(workers)} EnvPool workers, expected "
        f"{cfg.num_actor_processes}",
    )
    on_chip = {pid: ev for pid, ev in workers.items() if ev}
    check(not on_chip, f"EnvPool workers touched the chip: {on_chip}")

    return {
        "devices": len(devices), "wall_s": round(wall, 1),
        "env_steps": int(last["env_steps"]), "updates": updates,
        "skips": int(last["skips"]),
        "dropped_unrolls": int(last["dropped_unrolls"]),
        "grad_steps": grad["calls"], "act_steps": act["calls"],
        "first_loss": losses[0], "last_loss": losses[-1],
        **compiles.since(mark),
        "first_call_s": {
            "act": act["first_call_s"], "grad": grad["first_call_s"],
            "apply": apply["first_call_s"],
        },
        "step_programs": programs,
        "learn_batch": list(batch["obs"].shape),
        "state_on": device_ids(state.params)[0],
        "batch_shards_on": sorted(
            s.device.id for s in batch["obs"].addressable_shards
        ),
        "act_step_on": device_ids(act["out"][0])[0],
        "workers": len(workers), "workers_on_chip": len(on_chip),
    }


# --------------------------------------------------------------------------
# 4 + 5. the Pallas kernels and what 'auto' picks
# --------------------------------------------------------------------------


def phase_flash(compiles: CompileLog, shapes, **flash_kw) -> dict:
    """The forward and the backward kernel vs ``dense_attention`` (float32,
    "highest" matmul precision), causal with segment ids — the way
    TransformerNet calls them — at the default (256, 256) blocks."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from moolib_tpu.ops.attention import dense_attention, flash_attention

    cases = []
    for B, H, T, D, dtype in shapes:
        rng = np.random.default_rng(T + D)
        q, k, v, w = (
            jnp.asarray(rng.standard_normal((B, H, T, D)), dtype)
            for _ in range(4)
        )
        seg = jnp.asarray(
            np.cumsum(rng.random((B, T)) < 0.01, axis=1), jnp.int32
        )

        def weighted(fn):
            def loss(q, k, v):
                o = fn(q, k, v, causal=True, segment_ids=seg)
                return jnp.sum(o.astype(jnp.float32) * w), o

            return jax.jit(
                jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
            )

        with jax.default_matmul_precision("highest"):
            (_, o_ref), g_ref = weighted(dense_attention)(
                *(x.astype(jnp.float32) for x in (q, k, v))
            )
        flash = weighted(
            lambda *a, **kw: flash_attention(*a, **kw, **flash_kw)
        )
        if not flash_kw:
            # Compiled by Mosaic, not interpreted or replaced: the lowered
            # program carries the kernels as TPU custom calls.
            check(
                "tpu_custom_call" in flash.lower(q, k, v).as_text(),
                "flash_attention lowered without a Mosaic custom call",
            )
        mark = compiles.mark()
        (_, o), g = flash(q, k, v)
        jax.block_until_ready(g)
        rec = {
            "B": B, "H": H, "T": T, "D": D, "dtype": jnp.dtype(dtype).name,
            "compile_s": compiles.since(mark)["compile_s"],
        }
        for name, got, ref in zip(
            ("fwd", "dq", "dk", "dv"), (o, *g), (o_ref, *g_ref)
        ):
            got = np.asarray(got, np.float32)
            ref = np.asarray(ref, np.float32)
            check(np.isfinite(got).all(), f"flash {name} not finite at {rec}")
            rel = float(np.abs(got - ref).max() / np.abs(ref).max())
            rec[name + "_err"] = float(np.format_float_scientific(rel, 2))
            check(
                rel <= FLASH_TOL,
                f"flash {name} is {rel:.2e} of max|dense| away at {rec} "
                f"(tolerance {FLASH_TOL})",
            )
        check_platform((o, g), "flash")
        cases.append(rec)
    return {
        "tolerance": FLASH_TOL,
        "compile_s": round(sum(c["compile_s"] for c in cases), 2),
        "cases": cases,
    }


def phase_auto_backend(device, compiles: CompileLog, batch: int = 32,
                       unroll_length: int = 20) -> dict:
    """What ``attention(backend="auto")`` runs inside the jitted learner
    step of a TransformerNet at the shipped unroll length (T+1 frames),
    read back from the lowered program, and one step of it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from moolib_tpu.learner import (
        ImpalaConfig,
        make_impala_train_step,
        make_train_state,
    )
    from moolib_tpu.models import TransformerNet
    from moolib_tpu.ops.attention import resolve_backend

    T, B, A = unroll_length, batch, 6
    resolved = resolve_backend(T + 1, T + 1)
    net = TransformerNet(num_actions=A, compute_dtype=jnp.bfloat16)
    check(net.attention_backend == "auto", "TransformerNet default backend")
    rng = np.random.default_rng(0)
    batch = jax.device_put({
        "obs": rng.integers(0, 255, (T + 1, B, 84, 84, 4), dtype=np.uint8),
        "done": rng.random((T + 1, B)) < 0.02,
        "rewards": rng.standard_normal((T + 1, B)).astype(np.float32),
        "actions": rng.integers(0, A, (T, B)).astype(np.int32),
        "behavior_logits": rng.standard_normal((T, B, A)).astype(np.float32),
        "core_state": (),
    }, device)
    params = net.init(
        jax.random.PRNGKey(0), batch["obs"][:, :1], batch["done"][:, :1], ()
    )
    opt = optax.adam(6e-4)
    state = jax.device_put(make_train_state(params, opt), device)
    step = make_impala_train_step(
        net.apply, opt, ImpalaConfig(), donate=False
    )
    has_kernel = "tpu_custom_call" in step.lower(state, batch).as_text()
    check(
        has_kernel == (resolved == "flash"),
        f"auto resolves to {resolved!r} at T+1={T + 1} but the lowered "
        f"learner step {'has' if has_kernel else 'has no'} Mosaic kernel",
    )
    mark = compiles.mark()
    state, metrics = step(state, batch)
    loss = float(metrics["total_loss"])
    check(np.isfinite(loss), f"TransformerNet learner loss {loss}")
    check(int(state.step) == 1, "TransformerNet learner state.step")
    check_platform((state, metrics), "TransformerNet learner")
    return {
        "unroll_length": T, "frames": T + 1, "auto_resolves_to": resolved,
        "mosaic_kernel_in_learner_step": has_kernel,
        "auto_at_T2048": resolve_backend(2048, 2048),
        "loss": loss, **compiles.since(mark),
    }


# --------------------------------------------------------------------------


def main() -> int:
    t_start = time.perf_counter()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"platform={device['platform']} device_kind={device['kind']!r} "
        f"count={device['count']} jax={jax.__version__}", flush=True,
    )
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: found platform {device['platform']!r}, not a "
            "TPU; nothing was compiled, no result.", file=sys.stderr,
        )
        return 1

    from moolib_tpu import native
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"compile_cache={cache_dir}", flush=True)

    # Built here, now, from _native.cpp as committed; the EnvPool workers
    # load the same file.
    t0 = time.perf_counter()
    so = native.build_native(force=True)
    check(so is not None, "the native extension did not build")
    check(native.get_native() is not None, "the native extension did not load")
    native_info = {
        "so": os.path.basename(so),
        "build_s": round(time.perf_counter() - t0, 1),
    }
    print(f"native={native_info}", flush=True)

    compiles = CompileLog()
    phases = {}

    def report(name, result):
        phases[name] = result
        print(f"[{name}] {json.dumps(result)}", flush=True)

    per_chip = 256
    report("learner", run_learner(
        devices, per_chip * len(devices), LEARNER_STEPS, True, compiles
    ))
    if len(devices) > 1:
        report("dp_parity", phase_dp_parity(devices, per_chip, compiles))
    report("trainer", phase_trainer(devices, compiles))
    report("flash", phase_flash(compiles, [
        (2, 4, T, D, jnp.float32) for D in (32, 128) for T in (256, 2048)
    ] + [(2, 4, 2048, 32, jnp.bfloat16)]))
    report("auto_backend", phase_auto_backend(devices[0], compiles))

    report("summary", {
        "jax": jax.__version__,
        "native": native_info,
        "compile_cache": cache_dir,
        "wall_s": round(time.perf_counter() - t_start, 1),
        "compile_s": {
            name: result["compile_s"] for name, result in phases.items()
        },
        "claim": None,
    })
    print(result_line(device), flush=True)  # last on stdout
    return 0


if __name__ == "__main__":
    sys.exit(main())
