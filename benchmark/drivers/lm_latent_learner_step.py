"""Traffic ``lm_latent_learner_step``: ``lm_learner_step``'s cell for a
decoder whose description that driver cannot be told by data. The window,
the timing, the attention-backend check and the ``moe_*`` counters are
that driver's, reused; what differs, and why this is a file of its own:

- the parameter tree has stacked blocks, a multi-token-prediction module
  and correction biases: seeded, labelled and permuted by
  ``lib/seeded_latent.py``, and the attention's tiles counted over every
  block the step runs;
- the step's loss has the model's own term, so the reference is a whole
  loss function (``reference/<configuration>.py:loss_fn``) followed by
  ``lib/reference_latent.py``; ``mtp_loss`` of both sides is printed;
- the comparison goes leaf by leaf (``lib/reference_latent.py``: 37 GB of
  host memory otherwise); the process's peak resident memory is printed;
- a traced run also reads device time *under* ``moolib.lm.mtp``, whatever
  scope inside it an operation carries (``lib/readers_latent.py``).

Workload file keys: those of ``lm_learner_step`` but
``reference_columns``.
"""

from __future__ import annotations

import importlib
import os
import threading
import time

import jax
import numpy as np

from benchmark.drivers.learner_step import timed_steps
from benchmark.drivers.lm_learner_step import (COUNTERS, Recording,
                                               backends_traced)
from benchmark.lib import (harness, program, readers_latent,
                           reference_latent, scopes, seeded_latent, xplane)

MTP_COUNTERS = ("mtp_loss", "mtp_positions")


class HostWatch:
    """Says whose a long gap between completions was. A step here is half
    a second and two are in flight, so the host has half a second of slack
    and a gap that shows is a long one. A thread that only sleeps 50 ms at
    a time notes how late each wake-up came: if it overslept while the
    window's longest gap opened, the whole process stood still (the host);
    if it woke on time, the loop's thread was waiting on the device."""

    def __init__(self, period: float = 0.05):
        self.period, self.late = period, []  # (when, seconds overslept)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        last = time.perf_counter()
        while not self._stop.wait(self.period):
            now = time.perf_counter()
            self.late.append((now, now - last - self.period))
            last = now

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()

    def describe(self, start: float, done_at: list) -> str:
        gaps = [b - a for a, b in zip(done_at[:-1], done_at[1:])]
        i = int(np.argmax(gaps))
        lo, hi = done_at[i], done_at[i + 1]
        inside = [late for when, late in self.late if lo <= when <= hi + 0.1]
        return (
            f"longest gap {gaps[i] * 1e3:.1f} ms, before completion "
            f"{i + 1} of {len(done_at)}, {lo - start:.2f} s into the "
            f"window; the watching thread's latest wake-up inside it "
            f"{max(inside, default=0.0) * 1e3:.1f} ms late, in the whole "
            f"window {max((l for _, l in self.late), default=0.0) * 1e3:.1f}"
            f" ms; resident now {_resident_gb():.2f} GB"
        )


def _resident_gb() -> float:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 1e9


class Cell:
    """The compiled step and the reference of one cell; states and batches
    are made per seed."""

    def __init__(self, cell: dict, config: dict, devices):
        if len(devices) != 1:
            raise ValueError("lm_latent_learner_step runs one chip's share")
        self.cell, self.config, self.devices = cell, config, list(devices)
        self.T = cell["unroll_length"]
        self.B = cell["batch_per_chip"]
        self.model = config["model"]["kwargs"]
        self.net = program.build_model(config)
        self.shapes = seeded_latent.param_shapes(self.net)
        self.optimizer = program.build_optimizer(config)
        apply_fn = program.resolve(config["apply_factory"])(self.net)
        self.step = Recording(program.resolve(config["step_factory"])(
            apply_fn, self.optimizer, program.loss_config(config),
            mesh=None, donate=True,
        ))
        self.loss_fn = importlib.import_module(
            f"benchmark.reference.{config['reference']}"
        ).loss_fn
        self._followers = {}
        self.loads = jax.jit(program.resolve(config["router_loads_factory"])(
            self.net.clone(moe_buffer_rows=None)
        ))
        self.held = tuple(self.model["experts_held"])
        self._perms = {}  # seed -> each expert layer's order of columns

    def follower(self, precision: str) -> reference_latent.Follower:
        if precision not in self._followers:
            self._followers[precision] = reference_latent.Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        """The seeded weights, labelled as ``lm_learner_step`` labels
        them: found on the seed's first call, applied on later ones."""
        params = seeded_latent.make_params(
            self.shapes, seed, self.model,
            self.config["seeding"]["correction_bias_scale"],
        )
        if seed in self._perms:
            return seeded_latent.permute_routers(params, self._perms[seed])
        params, self._perms[seed], before, after = (
            seeded_latent.balance_held(
                params, self.loads, batch, self.held, seed
            )
        )
        print(f"[balance] seed {seed}: assignments held by layer as seeded "
              f"{before} (sum {sum(before)}), as labelled {after} (sum "
              f"{sum(after)})", flush=True)
        return params

    def held_by_layer(self, params, batch) -> list:
        first, count = self.held
        loads = np.asarray(self.loads(params, batch["obs"], batch["done"]))
        return [int(x) for x in loads[:, first:first + count].sum(axis=1)]

    def batch(self, seed: int):
        return seeded_latent.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            tiles=self.cell.get("attention_tiles"),
        )

    def state(self, params):
        return program.resolve("moolib_tpu.learner.make_train_state")(
            params, self.optimizer
        )

    def first_steps(self, state, batch):
        return reference_latent.program_first_steps(
            self.step, state, batch, self.cell["check_steps"],
            self.config["optimizer"]["decay"],
        )

    def worst_leaves(self, first, reference) -> str:
        """Which leaf each of the two leaf numbers was read off."""
        paths = [
            jax.tree_util.keystr(path) for path, _ in
            jax.tree_util.tree_flatten_with_path(self.shapes)[0]
        ]
        grad, change = reference_latent.leaf_gaps(first, reference)
        return (f"grad_leaf_gap at {paths[int(np.argmax(grad))]}, "
                f"change_leaf_gap at {paths[int(np.argmax(change))]}")

    def reference(self, seed, batch, against, precision="float32"):
        return self.follower(precision).follow(
            lambda: self.params(seed, batch), batch,
            self.cell["check_steps"], against=against,
        )


class calibration:
    """For ``tools/calibrate.py``: the numbers of one seed, sound and
    control, from the one compiled step."""

    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)

    def sound(self, seed):
        batch = self.c.batch(seed)
        state, first = self.c.first_steps(
            self.c.state(self.c.params(seed, batch)), batch
        )
        del state
        print(f"[moe] seed {seed}: {self.c.step.counters()}", flush=True)
        reference = self.c.reference(seed, batch, first["grad_abs"])
        print(f"[mtp] seed {seed}: program {first['mtp_losses']} reference "
              f"{reference['mtp_losses']}; worst leaves: "
              f"{self.c.worst_leaves(first, reference)}; [host] peak "
              f"resident {reference_latent.host_peak_gb():.2f} GB",
              flush=True)
        return reference_latent.numbers(first, reference)

    def control(self, seed, precision):
        batch = self.c.batch(seed)
        first = self.c.reference(seed, batch, None, precision)
        reference = self.c.reference(seed, batch, first["grad_abs"])
        return reference_latent.numbers(first, reference)


def run(ctx) -> dict:
    cell, config = ctx.cell, ctx.config
    clock = harness.PhaseClock()
    c = Cell(cell, config, ctx.devices)
    clock.mark("build")
    batch = c.batch(ctx.seed)
    state = c.state(c.params(ctx.seed, batch))
    jax.block_until_ready((state, batch))
    clock.mark("inputs")

    before = backends_traced()
    state, first = c.first_steps(state, batch)
    ran = {b: int(n - before[b]) for b, n in backends_traced().items()
           if n > before[b]}
    want = cell["attention_backend"]
    print(f"[attention] calls traced into the step at T+1={c.T + 1}, by "
          f"backend: {ran}; cell wants {want!r}", flush=True)
    ctx.verdict.hold(
        "attention_backend_differs",
        sum(n for b, n in ran.items() if b != want) + (want not in ran), 0,
        exact=True,
    )
    for _ in range(cell["warmup_steps"]):
        state, metrics = c.step(state, batch)
    float(metrics["total_loss"])
    held_at_start = c.held_by_layer(state.params, batch)
    dispatched = cell["check_steps"] + cell["warmup_steps"]
    clock.mark("first_steps_and_warmup")
    print(f"[phases] {clock}", flush=True)

    trace = scope_seconds = under = None
    traced_s = 0.0
    if ctx.trace:
        traced_s = min(cell["trace_seconds"], ctx.seconds / 2)
        ctx.start_trace()
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _, traced, _ = timed_steps(
                c.step, state, batch, traced_s, cell["in_flight"]
            )
        trace = ctx.stop_trace()
        planes = scopes.load(xplane.find_xplane(ctx.trace_dir))
        window = scopes.window_of(trace)
        scope_seconds = scopes.scope_seconds(planes, window)
        under = readers_latent.seconds_under(
            planes, window, readers_latent.MTP_SCOPE
        )
        print("[scopes] device seconds in the traced window: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                scope_seconds.items(), key=lambda kv: -kv[1]
            )
        ) + f"; under {readers_latent.MTP_SCOPE}, whatever scope inside "
            f"it: {under:.4f}", flush=True)
        dispatched += len(traced)

    window_start = time.monotonic()  # the clock the harness counts set-up on
    with HostWatch() as watch:
        state, start, done_at, losses = timed_steps(
            c.step, state, batch, ctx.seconds - traced_s, cell["in_flight"]
        )
    print(f"[stalls] {watch.describe(start, done_at)}", flush=True)
    seconds = done_at[-1] - start
    steps = len(done_at)
    compiled = ctx.compiles.between(window_start, time.monotonic())
    ctx.verdict.hold("compiles_in_window", len(compiled), 0, exact=True)
    ctx.verdict.hold(
        "steps_not_applied", dispatched + steps - int(state.step), 0,
        exact=True,
    )
    ctx.verdict.hold("moe_overflow", c.step.overflow(), 0, exact=True)
    counters = c.step.counters()
    counters.update({k: float(c.step.last[k]) for k in MTP_COUNTERS})
    print("[moe] " + ", ".join(
        f"{k} {counters[k]:.6g}" for k in COUNTERS + MTP_COUNTERS
    ), flush=True)
    held_at_end = c.held_by_layer(state.params, batch)
    print(f"[routing] assignments held by layer when the window opened "
          f"{held_at_start} (sum {sum(held_at_start)}) and when it closed "
          f"{held_at_end} (sum {sum(held_at_end)})", flush=True)
    failed = sum(1 for x in losses if not np.isfinite(x))
    gaps_ms = [(b - a) * 1e3 for a, b in zip(done_at[:-1], done_at[1:])]
    print(f"[window] {steps} steps in {seconds:.3f} s; between completions "
          f"median {harness.percentile(gaps_ms, 50):.3f} ms, p95 "
          f"{harness.percentile(gaps_ms, 95):.3f} ms, longest "
          f"{max(gaps_ms):.3f} ms", flush=True)

    # The plain reference, once the program's state is freed: neither its
    # seconds nor its memory are the program's.
    memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del state
    t0 = time.perf_counter()
    reference = c.reference(ctx.seed, batch, first["grad_abs"])
    print(f"[reference] {cell['check_steps']} steps in "
          f"{time.perf_counter() - t0:.2f} s after the window", flush=True)
    print(f"[mtp] mtp_loss by step: program {first['mtp_losses']}, "
          f"reference {reference['mtp_losses']}; total loss: program "
          f"{first['losses']}, reference {reference['losses']}", flush=True)
    print(f"[host] peak resident memory of the process "
          f"{reference_latent.host_peak_gb():.2f} GB; worst leaves: "
          f"{c.worst_leaves(first, reference)}", flush=True)
    ctx.verdict.hold_all(
        reference_latent.numbers(first, reference), cell["limits"]
    )
    return {
        "window_start": window_start,
        "memory_peak_bytes": memory_peak_bytes,
        "attempted": steps,
        "failed": failed,
        "end_to_end": {
            "learner_env_steps_per_s": steps * c.T * c.B / seconds,
            "learner_step_ms_p95": harness.percentile(gaps_ms, 95),
        },
        "readings": {
            "trace": trace,
            "steps_per_s": steps / seconds,
            "frames_per_step_per_chip": (c.T + 1) * c.B,
            "program": "jit_step",
            "scope_seconds": scope_seconds,
            "seconds_under_mtp": under,
            "counters": counters,
            "done_column": np.asarray(batch["done"])[:, 0],
            "attention_backend": "+".join(sorted(ran)),
        },
    }
