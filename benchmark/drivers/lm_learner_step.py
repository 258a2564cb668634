"""Traffic ``lm_learner_step``: a decoder language model as a token-level
V-trace learner. One device-resident learn batch of packed token sequences
made from the seed, and the configuration's jitted train step called from
Python one step at a time with a bounded number in flight: the window, the
first steps and the comparison are ``drivers/learner_step.py``'s, reused.

What differs from that driver, and why it is a driver of its own:

- observations are token ids and the action is the next token
  (``lib/seeded_lm.py``), and the model's ``apply`` comes through the
  configuration's ``apply_factory``, which adds the expert layers' counters
  to the step's metrics;
- the seeded float32 weights (1.9 GB at the benchmark's size) do not stay on
  the chip through the window: the step is handed them (and donates them),
  and they are made again from the seed once the window has closed;
- the experts held are those that carry the host's mean load at the seeded
  weights (``seeded_lm.balance_held``, with the program's own count of its
  routing, ``router_loads_factory``), so that every seed gives the step the
  same work; a ``[balance]`` line prints what each layer held as seeded and
  as labelled, and a ``[routing]`` line what it held when the window
  closed (the weights train on the one batch, and the routing drifts);
- ``correct`` also holds, exactly: ``moe_overflow`` 0 (no assignment fell
  off the expert layers' buffer, in any step followed or timed), and that
  every attention call traced into the step runs the backend the cell's
  file names (the program counts its calls by backend where it traces
  them);
- a traced run also reads device time by named scope (``lib/scopes.py``).

Workload file keys: those of ``learner_step``, ``attention_backend``
(what the step's attention calls have to run at this cell's shape) and,
optional, ``attention_tiles`` (the tiles every seed's episode boundaries
leave the attention kernels: ``seeded_lm.draw_done``).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark.drivers.learner_step import timed_steps
from benchmark.lib import (compare, harness, program, reference_train,
                           scopes, seeded_lm, xplane)

BACKENDS = ("dense", "blockwise", "flash")
COUNTERS = (
    "moe_assignments_held", "moe_assignments_total", "moe_tokens_unserved",
    "moe_load_max", "moe_load_mean", "moe_spills", "moe_overflow",
)


class Recording:
    """The step, keeping the metrics of the last call and the largest
    ``moe_overflow`` a call reported (read back when asked, not in the
    window)."""

    def __init__(self, step):
        self.step, self.last, self._overflows = step, None, []

    def __call__(self, state, batch):
        state, metrics = self.step(state, batch)
        self.last = metrics
        self._overflows.append(metrics["moe_overflow"])
        return state, metrics

    def counters(self) -> dict:
        return {k: float(self.last[k]) for k in COUNTERS}

    def overflow(self) -> float:
        seen, self._overflows = self._overflows, []
        return max((float(x) for x in seen), default=0.0)


def backends_traced() -> dict:
    """Attention calls traced in this process so far, by the backend each
    runs: the program's own record (``ops.attention.attention``)."""
    registry = program.resolve(
        "moolib_tpu.telemetry.global_telemetry"
    )().registry
    return {
        b: registry.value("attention_calls_traced_total", backend=b) or 0
        for b in BACKENDS
    }


class Cell:
    """The compiled step and the reference of one cell; states and batches
    are made per seed."""

    def __init__(self, cell: dict, config: dict, devices):
        if len(devices) != 1:
            raise ValueError("lm_learner_step runs one chip's share")
        self.cell, self.config, self.devices = cell, config, list(devices)
        self.T = cell["unroll_length"]
        self.B = cell["batch_per_chip"]
        self.net = program.build_model(config)
        self.shapes = seeded_lm.param_shapes(self.net)
        self.optimizer = program.build_optimizer(config)
        # Resolved at call time, so that a test can break the program
        # underneath the harness.
        apply_fn = program.resolve(config["apply_factory"])(self.net)
        self.step = Recording(program.resolve(config["step_factory"])(
            apply_fn, self.optimizer, program.loss_config(config),
            mesh=None, donate=True,
        ))
        self.follower = reference_train.Followers(
            config, cell["reference_columns"], self.devices[0]
        )
        # The routing is the weights' and the tokens', not the buffer's:
        # counted over one buffer a layer, half the program to compile.
        self.loads = jax.jit(program.resolve(config["router_loads_factory"])(
            self.net.clone(moe_buffer_rows=None)
        ))
        self.held = tuple(config["model"]["kwargs"]["experts_held"])
        self._perms = {}  # seed -> each layer's order of router columns

    def params(self, seed: int, batch):
        """The seeded weights, their routers' columns in the order that
        seats the mean load at the experts held: found on the seed's first
        call (some forward passes), applied on every later one."""
        params = seeded_lm.make_params(self.shapes, seed)
        if seed in self._perms:
            return seeded_lm.permute_routers(params, self._perms[seed])
        params, self._perms[seed], before, after = seeded_lm.balance_held(
            params, self.loads, batch, self.held, seed
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
        return seeded_lm.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            tiles=self.cell.get("attention_tiles"),
        )

    def state(self, params):
        """The step donates its state, the weights in it: they are gone
        after the first step, and :meth:`params` makes them again."""
        return program.resolve("moolib_tpu.learner.make_train_state")(
            params, self.optimizer
        )

    def first_steps(self, state, batch):
        return program.first_steps(
            self.step, state, batch, self.cell["check_steps"],
            self.config["optimizer"]["decay"],
        )


class calibration:
    """For ``tools/calibrate.py``: the numbers of one seed, sound and
    control, from the one compiled step."""

    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)

    def _reference(self, seed):
        steps = self.c.cell["check_steps"]
        batch = self.c.batch(seed)
        return batch, steps, self.c.follower("float32").follow(
            self.c.params(seed, batch), batch, steps
        )

    def sound(self, seed):
        batch, _, reference = self._reference(seed)
        _, first = self.c.first_steps(
            self.c.state(self.c.params(seed, batch)), batch
        )
        print(f"[moe] seed {seed}: {self.c.step.counters()}", flush=True)
        return compare.training_numbers(first, reference)

    def control(self, seed, precision):
        batch, steps, reference = self._reference(seed)
        first = self.c.follower(precision).follow(
            self.c.params(seed, batch), batch, steps
        )
        return compare.training_numbers(first, reference)


def run(ctx) -> dict:
    cell, config = ctx.cell, ctx.config
    clock = harness.PhaseClock()
    c = Cell(cell, config, ctx.devices)
    clock.mark("build")
    batch = c.batch(ctx.seed)
    state = c.state(c.params(ctx.seed, batch))
    jax.block_until_ready((state, batch))
    clock.mark("inputs")

    # The step is traced in its first call: what its attention calls run
    # is read off the program's own record of that trace.
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

    trace = scope_seconds = None
    traced_s = 0.0
    if ctx.trace:
        traced_s = min(cell["trace_seconds"], ctx.seconds / 2)
        ctx.start_trace()
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _, traced, _ = timed_steps(
                c.step, state, batch, traced_s, cell["in_flight"]
            )
        trace = ctx.stop_trace()
        scope_seconds = scopes.scope_seconds(
            scopes.load(xplane.find_xplane(ctx.trace_dir)),
            scopes.window_of(trace),
        )
        print("[scopes] device seconds in the traced window: " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                scope_seconds.items(), key=lambda kv: -kv[1]
            )
        ), flush=True)
        dispatched += len(traced)

    window_start = time.monotonic()  # the clock the harness counts set-up on
    state, start, done_at, losses = timed_steps(
        c.step, state, batch, ctx.seconds - traced_s, cell["in_flight"]
    )
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
    print(f"[moe] held {counters['moe_assignments_held']:.0f} of "
          f"{counters['moe_assignments_total']:.0f} assignments over the "
          f"layers (a share of "
          f"{counters['moe_assignments_held'] / counters['moe_assignments_total']:.4f}"
          f"), {counters['moe_tokens_unserved']:.0f} tokens with no expert "
          f"here, fullest expert {counters['moe_load_max']:.1f} against a "
          f"mean of {counters['moe_load_mean']:.1f} (layer means), "
          f"{counters['moe_spills']:.0f} layers spilled to the worst-case "
          f"buffer, overflow {counters['moe_overflow']:.0f}", flush=True)
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

    # The plain reference, once the program's state is freed and the
    # seeded weights are made again: neither its seconds nor its memory
    # are the program's.
    memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del state
    t0 = time.perf_counter()
    reference = c.follower("float32").follow(
        c.params(ctx.seed, batch), batch, cell["check_steps"]
    )
    print(f"[reference] {cell['check_steps']} steps in "
          f"{time.perf_counter() - t0:.2f} s after the window", flush=True)
    ctx.verdict.hold_all(
        compare.training_numbers(first, reference), cell["limits"]
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
            "counters": counters,
            "done_column": np.asarray(batch["done"])[:, 0],
            "attention_backend": "+".join(sorted(ran)),
        },
    }
