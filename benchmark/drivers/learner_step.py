"""Traffic ``learner_step``: one device-resident learn batch made from the
seed, and the configuration's jitted train step called from Python one step
at a time, as a user's loop calls it, with a bounded number in flight.

Workload file keys: ``unroll_length``, ``batch_per_chip``, ``done_rate``,
``in_flight`` (block on step i-in_flight before dispatching step i),
``check_steps``, ``reference_columns``, ``warmup_steps``, ``trace_seconds``,
``limits``.
"""

from __future__ import annotations

import collections
import time

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.lib import compare, harness, program, reference_train, seeded


class Cell:
    """The compiled step, the shardings and the reference of one cell;
    states and batches are made per seed."""

    def __init__(self, cell: dict, config: dict, devices):
        self.cell, self.config, self.devices = cell, config, list(devices)
        self.T = cell["unroll_length"]
        self.B = cell["batch_per_chip"] * len(self.devices)
        self.net = program.build_model(config)
        self.shapes = program.param_shapes(self.net, config)
        self.optimizer = program.build_optimizer(config)
        self.mesh = None
        self.replicated = self.batch_sharding = None
        if len(self.devices) > 1:
            make_mesh = program.resolve("moolib_tpu.parallel.mesh.make_mesh")
            self.mesh = make_mesh(dp=len(self.devices), devices=self.devices)
            self.replicated = NamedSharding(self.mesh, P())
            columns = NamedSharding(self.mesh, P(None, "dp"))
            self.batch_sharding = {
                "obs": columns, "done": columns, "rewards": columns,
                "actions": columns, "behavior_logits": columns,
                "core_state": NamedSharding(self.mesh, P("dp")),
            }
        # Resolved at call time, so that a test can break the step
        # underneath the harness.
        self.step = program.resolve(config["step_factory"])(
            self.net.apply, self.optimizer, program.loss_config(config),
            mesh=self.mesh, donate=True,
        )
        self.follower = reference_train.Followers(
            config, cell["reference_columns"], self.devices[0]
        )

    def inputs(self, seed: int):
        params = seeded.make_params(self.shapes, seed, self.replicated)
        batch = seeded.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            self.batch_sharding,
        )
        return params, batch

    def state(self, params):
        make = program.resolve("moolib_tpu.learner.make_train_state")
        # The step donates its state: hand it a copy, the seeded weights
        # stay for the reference.
        state = make(jax.tree_util.tree_map(jax.numpy.copy, params),
                     self.optimizer)
        if self.mesh is not None:
            state = program.resolve("moolib_tpu.learner.replicate_state")(
                state, self.mesh
            )
        return state

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
        params, batch = self.c.inputs(seed)
        steps = self.c.cell["check_steps"]
        return params, batch, steps, self.c.follower("float32").follow(
            params, batch, steps
        )

    def sound(self, seed):
        params, batch, _, reference = self._reference(seed)
        _, first = self.c.first_steps(self.c.state(params), batch)
        return compare.training_numbers(first, reference)

    def control(self, seed, precision):
        params, batch, steps, reference = self._reference(seed)
        first = self.c.follower(precision).follow(params, batch, steps)
        return compare.training_numbers(first, reference)


def timed_steps(step, state, batch, seconds: float, in_flight: int):
    """Call ``step`` for ``seconds``; block on step i-in_flight before
    dispatching step i. Returns the state, the window's start, each step's
    completion time as the host saw it, and every loss."""
    pending = collections.deque()
    done_at, losses = [], []
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        if len(pending) >= in_flight:
            with jax.profiler.TraceAnnotation("bench.wait"):
                losses.append(float(pending.popleft()))
            done_at.append(time.perf_counter())
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            state, metrics = step(state, batch)
        pending.append(metrics["total_loss"])
    while pending:
        with jax.profiler.TraceAnnotation("bench.wait"):
            losses.append(float(pending.popleft()))
        done_at.append(time.perf_counter())
    return state, start, done_at, losses


def run(ctx) -> dict:
    cell, config = ctx.cell, ctx.config
    clock = harness.PhaseClock()
    c = Cell(cell, config, ctx.devices)
    clock.mark("build")
    params, batch = c.inputs(ctx.seed)
    jax.block_until_ready((params, batch))
    clock.mark("inputs")

    # One object, the compiled step with its state: driven through its
    # first steps, then handed to the window. The reference follows the
    # same steps once the window has closed and the state is gone.
    state = c.state(params)
    state, first = c.first_steps(state, batch)
    for _ in range(cell["warmup_steps"]):
        state, metrics = c.step(state, batch)
    float(metrics["total_loss"])
    dispatched = cell["check_steps"] + cell["warmup_steps"]
    clock.mark("first_steps_and_warmup")
    print(f"[phases] {clock}", flush=True)

    trace = None
    traced_s = 0.0
    if ctx.trace:
        traced_s = min(cell["trace_seconds"], ctx.seconds / 2)
        ctx.start_trace()
        with jax.profiler.TraceAnnotation("bench.window"):
            state, _, traced, _ = timed_steps(
                c.step, state, batch, traced_s, cell["in_flight"]
            )
        trace = ctx.stop_trace()
        dispatched += len(traced)

    window_start = time.monotonic()  # the clock the harness counts set-up on
    state, start, done_at, losses = timed_steps(
        c.step, state, batch, ctx.seconds - traced_s, cell["in_flight"]
    )
    seconds = done_at[-1] - start
    steps = len(done_at)
    compiled = ctx.compiles.between(window_start, time.monotonic())
    ctx.verdict.hold("compiles_in_window", len(compiled), 0, exact=True)
    # Every step dispatched advanced the state it was handed.
    ctx.verdict.hold(
        "steps_not_applied", dispatched + steps - int(state.step), 0,
        exact=True,
    )
    failed = sum(1 for x in losses if not np.isfinite(x))
    # The time between successive completions as the host saw them (the
    # first completion includes the time to fill the queue: it only opens
    # the first gap).
    gaps_ms = [(b - a) * 1e3 for a, b in zip(done_at[:-1], done_at[1:])]
    env_steps = steps * c.T * c.B
    print(f"[window] {steps} steps in {seconds:.3f} s; between completions "
          f"median {harness.percentile(gaps_ms, 50):.3f} ms, p95 "
          f"{harness.percentile(gaps_ms, 95):.3f} ms, longest "
          f"{max(gaps_ms):.3f} ms", flush=True)

    # The plain reference, in blocks of columns, after the program's state
    # is freed: neither its seconds nor its memory are the program's.
    memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)
    del state
    t0 = time.perf_counter()
    reference = c.follower("float32").follow(
        params, batch, cell["check_steps"]
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
            "learner_env_steps_per_s": env_steps / seconds / len(ctx.devices),
            "learner_step_ms_p95": harness.percentile(gaps_ms, 95),
        },
        "readings": {
            "trace": trace,
            "steps_per_s": steps / seconds,
            "frames_per_step_per_chip": (c.T + 1) * c.B // len(ctx.devices),
            "program": "jit_" + getattr(
                getattr(c.step, "__wrapped__", c.step), "__name__", "step"
            ),
        },
    }
