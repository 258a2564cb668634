"""Traffic ``vtrace_loop``: ``examples/vtrace/experiment.train`` itself, a
closed loop of EnvPool workers, acting, batching, the Accumulator and the
learner, for as long as the window lasts.

``correct`` is a function of ``--seed`` and the code alone:

(a) before ``train()``, the act, gradient and apply steps built by the same
    factories with the same arguments as ``train()`` builds them, driven
    through their first steps on a learn batch made from the seed, as a
    learner cell's step is; the plain reference follows the same steps after
    ``train()`` has returned. Built alike, ``train()``'s own copies of these
    programs come out of the compile cache;
(b) after the window, only what holds under every schedule (see
    :func:`invariants`). How many updates, skips and drops an interval holds
    is the scheduler's doing: those are metrics, never a verdict.

``train()`` blocks its caller and calls ``log_fn`` in the loop's thread at
every log row: that is the hook for time stamps, stepscope readings and the
profiler, with no change to the program. The loop reads ``cfg.max_seconds``
every iteration, so the hook ends the run by setting it to 0 when the window
has closed.

Workload file keys: ``train_config`` (VtraceConfig fields), ``warm_rows``,
``min_updates_before_window``, ``max_run_seconds`` (a stop if the window
never opens), ``check_steps``, ``reference_columns``, ``trace_seconds``,
``limits``.
"""

from __future__ import annotations

import importlib
import math
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import compare, harness, program, reference_train, seeded

ROW = re.compile(r"steps\s+(\d+)\s.*updates\s+([0-9.e+]+)\s*$")
LOOP = "vtrace_learner"  # the StepScope name train() gives its loop


class Programs:
    """The loop's three programs, built as ``train()`` builds them."""

    def __init__(self, cell: dict, config: dict, devices):
        tc = cell["train_config"]
        self.cell, self.config = cell, config
        self.device = devices[0]
        self.learn_batch = tc["learn_batch_size"]
        self.actor_batch = tc["actor_batch_size"]
        self.T = tc["unroll_length"]
        self.net = program.build_model(config)
        self.shapes = program.param_shapes(self.net, config)
        self.optimizer = program.build_optimizer(config)
        learner = "moolib_tpu.learner."
        self.act = program.resolve(learner + "make_act_step")(self.net.apply)
        self.grad = program.resolve(learner + "make_grad_step")(
            self.net.apply, config=program.loss_config(config), mesh=None,
            grad_scale=float(self.learn_batch),
        )
        self.apply = program.resolve(learner + "make_apply_step")(
            self.optimizer, donate=True
        )
        self.follower = reference_train.Followers(
            config, cell["reference_columns"], self.device
        )

    def inputs(self, seed: int):
        params = seeded.make_params(self.shapes, seed)
        batch = seeded.make_learn_batch(
            seed, self.config, self.T, self.learn_batch, 0.02
        )
        return params, batch

    def update(self, state, batch):
        """One update as the loop makes it with one peer and a virtual
        batch of one learn batch: the gradient step's batch-sum gradients
        go through the Accumulator, come back as the mean over the batch,
        on the host, and are applied."""
        grads, metrics = self.grad(state.params, batch)
        mean = jax.tree_util.tree_map(
            lambda g: jnp.asarray(np.asarray(g) / self.learn_batch), grads
        )
        return self.apply(state, mean), metrics

    def first_steps(self, params, batch):
        make = program.resolve("moolib_tpu.learner.make_train_state")
        state = make(jax.tree_util.tree_map(jnp.copy, params), self.optimizer)
        _, numbers = program.first_steps(
            self.update, state, batch, self.cell["check_steps"],
            self.config["optimizer"]["decay"],
        )
        return numbers

    def act_logits(self, params, act_in, seed: int):
        """The act step on one actor batch of frames (``act_in`` has the
        time axis the learner's forward wants; the act step adds its own).
        Its logits are what V-trace later takes as the behaviour
        policy's."""
        obs, done, core = act_in
        actions, logits, _ = self.act(
            params, seeded.key_from_seed(seed),
            jax.tree_util.tree_map(lambda x: x[0], obs), done[0], core,
        )
        return np.asarray(actions), np.asarray(logits, np.float64)


def act_gap(logits, reference_logits) -> float:
    """The widest gap between the act step's logits and the reference's,
    over the root mean square of the reference's."""
    reference_logits = np.asarray(reference_logits, np.float64)
    scale = math.sqrt(float(np.mean(reference_logits ** 2)))
    return float(np.max(np.abs(logits - reference_logits)) / scale)


def seeded_inputs(programs: Programs, seed: int):
    """Part (a)'s inputs: seeded weights, one learn batch, and one actor
    batch of its frames for the act step."""
    params, batch = programs.inputs(seed)
    n = programs.actor_batch
    act_in = (
        jax.tree_util.tree_map(lambda x: x[:1, :n], batch["obs"]),
        batch["done"][:1, :n],
        tuple(x[:n] for x in batch["core_state"]),
    )
    return params, batch, act_in


def one_side(programs: Programs, inputs, seed: int, precision=None):
    """One side of part (a): its first steps, its act logits and, for the
    program, its actions. ``precision`` None is the program; "float32" the
    reference; a lower one the control in the program's place."""
    params, batch, act_in = inputs
    if precision is None:
        actions, logits = programs.act_logits(params, act_in, seed)
        return programs.first_steps(params, batch), logits, actions
    follower = programs.follower(precision)
    first = follower.follow(params, batch, programs.cell["check_steps"])
    logits = np.asarray(follower.forward(params, *act_in)[0][0], np.float64)
    return first, logits, None


def seeded_numbers(programs: Programs, side, reference) -> dict:
    """Part (a): the numbers compared, ``side`` against ``reference``."""
    first, logits, actions = side
    numbers = compare.training_numbers(first, reference[0])
    numbers["act_logit_gap"] = act_gap(logits, reference[1])
    if actions is not None:
        A = programs.config["num_actions"]
        numbers["act_actions_out_of_range"] = int(
            np.sum((actions < 0) | (actions >= A))
        )
    return numbers


class calibration:
    """For ``tools/calibrate.py``: part (a)'s numbers of one seed."""

    def __init__(self, cell, config, devices):
        self.programs = Programs(cell, config, devices)

    def _numbers(self, seed, precision):
        inputs = seeded_inputs(self.programs, seed)
        return seeded_numbers(
            self.programs, one_side(self.programs, inputs, seed, precision),
            one_side(self.programs, inputs, seed, "float32"),
        )

    def sound(self, seed):
        return self._numbers(seed, None)

    def control(self, seed, precision):
        return self._numbers(seed, precision)


class Watch:
    """``log_fn``: runs in the loop's thread at every row. Opens the window
    once the loop is warm, closes it after ``seconds``, ends the run."""

    def __init__(self, ctx, cfg, telemetry):
        self.ctx, self.cfg, self.telemetry = ctx, cfg, telemetry
        cell = ctx.cell
        self.warm_rows = cell["warm_rows"]
        self.min_updates = cell["min_updates_before_window"]
        self.traced_s = (
            min(cell["trace_seconds"], ctx.seconds / 2) if ctx.trace else 0.0
        )
        self.seconds = ctx.seconds - self.traced_s
        self.rows = 0
        self.updates = 0.0
        self.last_row_at = time.monotonic()
        self.phase = "warm"
        self.trace = None
        self.trace_span = None
        self.start = self.end = None  # (row index, monotonic, readings)

    def readings(self):
        snap = self.telemetry.global_telemetry().snapshot()
        scope = self.telemetry.summarize_stepscope(snap).get(LOOP, {})
        counters = {
            name: sum(
                float(series.get("value", 0.0))
                for sid, series in snap.items() if sid.startswith(name)
            )
            for name in ("envpool_worker_deaths_total",
                         "envpool_respawns_total",
                         "envpool_env_errors_total")
        }
        return {"stepscope": scope, "counters": counters}

    def __call__(self, line: str) -> None:
        now = time.monotonic()
        match = ROW.search(line)
        if match is None:
            raise RuntimeError(f"train() logged a row this harness cannot "
                               f"read: {line!r}")
        index, self.rows = self.rows, self.rows + 1
        updates = float(match.group(2))
        # Both ends of the window are rows that show a new update: the rate
        # of whole updates is then taken between two of them, and is off by
        # a row's interval at most, not by an update's.
        applied, self.updates = updates > self.updates, updates
        since, self.last_row_at = self.last_row_at, now
        if self.phase == "warm":
            warm = (
                self.rows >= self.warm_rows
                and updates >= self.min_updates
                and not self.ctx.compiles.between(since, now)
            )
            if warm and self.ctx.trace:
                self.ctx.start_trace()
                # Opened and closed by hand: the window spans many calls
                # of this hook, all in the loop's thread.
                self.mark = jax.profiler.TraceAnnotation("bench.window")
                self.mark.__enter__()
                self.phase, self.trace_span = "traced", [now, None]
            elif warm and applied:
                self.phase = "window"
                self.start = (index, now, self.readings())
        elif self.phase == "traced":
            if now - self.trace_span[0] >= self.traced_s:
                self.trace_span[1] = now
                self.mark.__exit__(None, None, None)
                self.trace = self.ctx.stop_trace()
                self.phase = "settle"  # this row paid for stop_trace
        elif self.phase == "settle":
            if applied:
                self.phase = "window"
                self.start = (index, now, self.readings())
        elif self.phase == "window":
            if now - self.start[1] >= self.seconds and applied:
                self.end = (index, now, self.readings())
                self.phase = "done"
                self.cfg.max_seconds = 0.0  # the loop reads it every turn


def invariants(rows, start: int, end: int, verdict) -> int:
    """Part (b): what holds under every schedule. Returns the number of
    rows that break it (the run's ``failed`` from the loop's side).

    A row's ``total_loss`` is a mean over the gradient steps whose metrics
    were drained in its interval, and is NaN when there were none. Which
    interval a gradient step lands in is the scheduler's doing, so a single
    row's NaN says nothing. But a non-finite loss poisons the parameters
    for good, and every applied update has had its gradient step, of which
    at most ``parallel_gradients`` (2) can be ahead of the updates. So: among
    the rows after the last one by which all but the final three updates
    had applied, at least one must hold a gradient step, and if any of them
    is finite, every step before it was."""
    col = lambda name: [float(r[name]) for r in rows]  # noqa: E731
    for name in ("env_steps", "updates"):
        xs = col(name)
        verdict.hold(
            f"rows_where_{name}_decreased",
            sum(1 for a, b in zip(xs, xs[1:]) if b < a), 0, exact=True,
        )
    updates = col("updates")
    applied = updates[end] - updates[start]
    verdict.hold("window_without_update", int(applied < 1), 0, exact=True)
    tail = max(
        (i for i in range(start, end) if updates[end] - updates[i] >= 3),
        default=None,
    )
    broken = 0
    if tail is not None:
        finite = [
            all(math.isfinite(float(rows[i][k]))
                for k in ("total_loss", "entropy", "grad_norm"))
            for i in range(tail + 1, end + 1)
        ]
        broken = int(not any(finite))
    verdict.hold("last_updates_without_finite_loss", broken, 0, exact=True)
    return broken


def run(ctx) -> dict:
    cell, config = ctx.cell, ctx.config
    experiment = importlib.import_module(cell["program"])
    telemetry = importlib.import_module("moolib_tpu.telemetry")

    # (a) the loop's programs through their first steps on seeded inputs;
    # the reference follows once the loop has ended.
    clock = harness.PhaseClock()
    programs = Programs(cell, config, ctx.devices)
    inputs = seeded_inputs(programs, ctx.seed)
    program_side = one_side(programs, inputs, ctx.seed)
    del inputs  # the loop makes its own buffers; these are made again
    clock.mark("seeded_first_steps")

    cfg = experiment.VtraceConfig(
        **cell["train_config"], seed=ctx.seed % (2 ** 31),
        total_steps=10 ** 15, max_seconds=float(cell["max_run_seconds"]),
    )
    watch = Watch(ctx, cfg, telemetry)
    rows = experiment.train(cfg, log_fn=watch)
    clock.mark("train")
    print(f"[phases] {clock}", flush=True)
    memory_peak_bytes = harness.memory_peak_bytes(ctx.devices)

    if watch.start is None or watch.end is None:
        raise RuntimeError(
            f"the window never {'opened' if watch.start is None else 'closed'}"
            f" in {cell['max_run_seconds']} s: {len(rows)} rows, last "
            f"{rows[-1] if rows else None}"
        )
    (i0, t0, r0), (i1, t1, r1) = watch.start, watch.end
    a, b = rows[i0], rows[i1]
    seconds = b["time"] - a["time"]
    tc = cell["train_config"]
    updates = b["updates"] - a["updates"]
    env_steps = b["env_steps"] - a["env_steps"]
    act_calls = env_steps // tc["actor_batch_size"]
    # An actor batch hands over one unroll every T act calls.
    unrolls = act_calls / tc["unroll_length"]
    # Every row's time and counters, for whoever has to explain a spread.
    print("[rows] " + " ".join(
        f"{r['time'] - rows[0]['time']:.3f}:{r['env_steps']}:{r['updates']:g}"
        for r in rows
    ), flush=True)
    print(f"[window] rows {i0}..{i1}: {seconds:.3f} s, {updates:g} updates, "
          f"{env_steps} env steps acted, "
          f"{b['dropped_unrolls'] - a['dropped_unrolls']:g} unrolls dropped, "
          f"{b['skips'] - a['skips']:g} skips", flush=True)

    ctx.verdict.hold(
        "compiles_in_window", len(ctx.compiles.between(t0, t1)), 0,
        exact=True,
    )
    broken = invariants(rows, i0, i1, ctx.verdict)
    trouble = sum(r1["counters"].values()) - sum(r0["counters"].values())
    ctx.verdict.hold("env_worker_deaths_respawns_errors", trouble, 0,
                     exact=True)

    # The plain reference, in blocks of columns, now that the loop's state
    # is gone: neither its seconds nor its memory are the program's.
    t_ref = time.perf_counter()
    inputs = seeded_inputs(programs, ctx.seed)
    numbers = seeded_numbers(
        programs, program_side, one_side(programs, inputs, ctx.seed, "float32")
    )
    print(f"[reference] {time.perf_counter() - t_ref:.2f} s after the loop",
          flush=True)
    for name, value in numbers.items():
        ctx.verdict.hold(name, value, cell["limits"][name],
                         exact=name == "act_actions_out_of_range")
    return {
        "window_start": t0,
        "memory_peak_bytes": memory_peak_bytes,
        "attempted": int(updates + act_calls),
        "failed": int(broken * updates + trouble),
        "end_to_end": {
            "loop_env_steps_per_s": updates * tc["virtual_batch_size"]
            * tc["unroll_length"] / seconds,
        },
        "readings": {
            "trace": watch.trace,
            "trace_window": watch.trace_span,
            "stepscope": (r0["stepscope"], r1["stepscope"]),
            "rows": (a, b),
            "unrolls_produced": unrolls,
            # The program has no spans yet: an idle gap has no owner.
            "unowned_gap": "(the program has no spans yet)",
        },
    }
