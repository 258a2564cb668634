import os
import signal
import threading
import time

import numpy as np
import pytest

from moolib_tpu.envpool import EnvPool, EnvStepper, WorkerDied, step_with_retry

from fake_env import BadEnv, CrashEnv, DictObsEnv, FakeEnv, PoisonEnv, SlowEnv


def _mirror_step(envs, states, actions):
    """In-process mirror of the worker loop's auto-reset semantics."""
    obs_out, rew_out, done_out = [], [], []
    for env, a in zip(envs, actions):
        obs, reward, done, _, _ = env.step(int(a))
        if done:
            obs, _ = env.reset()
        obs_out.append(obs)
        rew_out.append(reward)
        done_out.append(done)
    return np.stack(obs_out), np.array(rew_out, np.float32), np.array(done_out)


def test_envpool_matches_inprocess_mirror(rng):
    B, W = 8, 4
    with EnvPool(FakeEnv, num_processes=W, batch_size=B, num_batches=2) as pool:
        mirror = [FakeEnv(i) for i in range(B)]
        for e in mirror:
            e.reset()
        for step in range(100):
            b = step % 2
            actions = rng.integers(0, 5, (B,))
            fut = pool.step(b, actions)
            out = fut.result(timeout=10)
            m_obs, m_rew, m_done = _mirror_step(mirror, None, actions)
            np.testing.assert_array_equal(out["obs"], m_obs)
            np.testing.assert_allclose(out["reward"], m_rew)
            np.testing.assert_array_equal(out["done"], m_done)


def test_envpool_double_buffering_overlap(rng):
    B, W = 4, 2
    with EnvPool(FakeEnv, num_processes=W, batch_size=B, num_batches=2) as pool:
        f0 = pool.step(0, np.ones(B, np.int64))
        f1 = pool.step(1, np.zeros(B, np.int64))  # in flight simultaneously
        r0, r1 = f0.result(timeout=10), f1.result(timeout=10)
        # Same envs advanced twice: buffer 1 sees t one step further.
        assert (r1["episode_step"] == r0["episode_step"] + 1).all()


def test_envpool_busy_buffer_raises(rng):
    with EnvPool(FakeEnv, num_processes=1, batch_size=2, num_batches=1) as pool:
        fut = pool.step(0, np.zeros(2, np.int64))
        with pytest.raises(RuntimeError, match="in flight"):
            pool.step(0, np.zeros(2, np.int64))
        fut.result(timeout=10)
        pool.step(0, np.zeros(2, np.int64)).result(timeout=10)


def test_late_callback_sees_own_step_not_newer_buffer_state(rng):
    """ADVICE r4: a callback registered AFTER its step was collected — and
    after a newer step was dispatched on the same buffer — must observe the
    step it belongs to (the cached outcome), not a re-read of shared buffer
    state the newer step may have overwritten."""
    import threading

    B = 2
    with EnvPool(FakeEnv, num_processes=1, batch_size=B) as pool:
        f_old = pool.step(0, np.zeros(B, np.int64))
        r_old = f_old.result(timeout=10)
        old_step = np.array(r_old["episode_step"], copy=True)
        # Newer step in flight on the SAME buffer before the late
        # registration.
        f_new = pool.step(0, np.ones(B, np.int64))
        fired = threading.Event()
        seen = {}

        def cb(fut):
            seen["out"] = fut.result()
            fired.set()

        f_old.add_done_callback(cb)
        # Fires promptly with this future's CACHED collection — it must
        # not be re-registered against the newer in-flight step, and its
        # result() must not re-collect shared buffer state. (The numpy
        # views inside keep their documented lifetime: valid until the
        # buffer's next step; identity is the attribution guarantee.)
        assert fired.wait(5), "late callback never fired"
        assert seen["out"] is r_old
        r_new = f_new.result(timeout=10)
        assert (r_new["episode_step"] == old_step + 1).all()
        # The old future keeps answering with its own cached collection.
        assert f_old.result() is r_old


def test_envpool_dict_obs_and_episode_stats(rng):
    B = 4
    with EnvPool(DictObsEnv, num_processes=2, batch_size=B) as pool:
        returns = np.zeros(B)
        for step in range(12):
            out = pool.step(0, np.ones(B, np.int64)).result(timeout=10)
            assert out["pos"].shape == (B, 2) and out["vel"].shape == (B, 1)
            # episode_return reported includes this step's reward; resets after done
            assert (out["episode_step"] > 0).all()


def test_envpool_validation_errors():
    with pytest.raises(ValueError, match="divisible"):
        EnvPool(FakeEnv, num_processes=3, batch_size=4)
    with EnvPool(FakeEnv, num_processes=1, batch_size=2) as pool:
        with pytest.raises(IndexError):
            pool.step(5, np.zeros(2, np.int64))
        with pytest.raises(ValueError, match="action shape"):
            pool.step(0, np.zeros(3, np.int64))


def test_envpool_worker_startup_failure():
    with pytest.raises(RuntimeError, match="boom at construction"):
        EnvPool(BadEnv, num_processes=1, batch_size=1)


def test_envpool_device_staging(rng):
    import jax

    with EnvPool(
        FakeEnv, num_processes=2, batch_size=4, device=jax.devices()[0]
    ) as pool:
        out = pool.step(0, np.zeros(4, np.int64)).result(timeout=10)
        assert isinstance(out["obs"], jax.Array)
        assert out["obs"].shape == (4, 3)


def test_envstepper_alias():
    assert EnvStepper is EnvPool


def test_push_cmd_ring_wraparound():
    """The parent's head and the worker's shm tail (u32) must agree past
    2^32 dispatches: occupancy is computed in modular space (regression for
    a spurious 'command ring overflow' after 2^32 steps)."""
    import types

    from moolib_tpu.envpool import pool as pool_mod

    posts = []
    fake = types.SimpleNamespace(
        _rings=[(np.zeros(pool_mod._RING, np.uint32),
                 np.zeros(1, np.uint32))],
        _ring_heads=[0],
        _native=types.SimpleNamespace(
            sem_post=lambda buf, off: posts.append(off)
        ),
        _shm=types.SimpleNamespace(buf=None),
        _ctrl=types.SimpleNamespace(cmd_sems=[0]),
    )
    push = pool_mod.EnvPool._push_cmd

    # Park head/tail just below the u32 wrap, as after ~2^32 dispatches.
    start = 2**32 - 3
    fake._ring_heads[0] = start % 2**32
    fake._rings[0][1][0] = start % 2**32
    for i in range(8):  # crosses the wrap boundary
        push(fake, 0, i % pool_mod._RING)
        # Worker consumed it: advance the shm tail with u32 wrap semantics.
        fake._rings[0][1][0] = (int(fake._rings[0][1][0]) + 1) & 0xFFFFFFFF
    assert len(posts) == 8
    assert fake._ring_heads[0] == (start + 8) % 2**32

    # And a genuinely full ring still trips the overflow guard.
    fake._rings[0][1][0] = fake._ring_heads[0]
    for i in range(pool_mod._RING):
        push(fake, 0, 0)
    with pytest.raises(RuntimeError, match="overflow"):
        push(fake, 0, 0)


def test_notify_gate_stays_closed_without_callbacks():
    """Blocking-only pools must never accumulate notify-semaphore posts:
    workers gate their notify post on the shm flag, which only opens when a
    done-callback starts the drain thread (an ungated post per step would
    hit SEM_VALUE_MAX after ~2^31 steps and crash the worker)."""
    from fake_env import FakeEnv

    pool = EnvPool(FakeEnv, num_processes=2, batch_size=4, num_batches=2)
    try:
        if pool._ctrl is None:
            pytest.skip("native data plane unavailable (pipe mode)")
        flag = pool._ctrl.flag_view(pool._shm.buf)
        for _ in range(3):
            pool.step(0, np.zeros(4, np.int64)).result(timeout=30)
        assert flag[0] == 0  # gate closed: nothing registered a callback

        done = threading.Event()
        fut = pool.step(0, np.zeros(4, np.int64))
        fut.add_done_callback(lambda f: done.set())
        assert flag[0] == 1  # gate opened with the first callback
        assert done.wait(30)
        fut.result(timeout=0)
    finally:
        pool.close()


# -- supervision (ISSUE 12: survivable env tier) ------------------------------


def _retry_step(pool, b, a, deadline_s=30.0):
    """Drive retries until a step completes (respawn in progress raises
    WorkerDied fast; the restart budget bounds the phase)."""
    deadline = time.monotonic() + deadline_s
    while True:
        try:
            return pool.step(b, a).result(timeout=30)
        except WorkerDied:
            assert time.monotonic() < deadline, "pool never recovered"
            time.sleep(0.02)


def test_worker_kill_typed_error_and_exactly_once_retry():
    """SIGKILL one worker mid-batch: the in-flight future fails FAST with
    the typed WorkerDied (naming the worker), the pool respawns the slot,
    and the same-action retry is exactly-once — surviving slices advance
    by exactly one step (served from their written results, never
    re-stepped) while the killed slot's fresh envs start at step 1."""
    pool = EnvPool(SlowEnv, num_processes=2, batch_size=4, num_batches=2,
                   restart_backoff=0.05, name="t-kill")
    try:
        a = np.zeros(4, np.int64)
        pre = np.array(
            pool.step(0, a).result(timeout=30)["episode_step"], copy=True
        )
        fut = pool.step(0, a)
        time.sleep(0.05)  # mid-batch: SlowEnv steps take 0.15s each
        os.kill(pool._procs[0].pid, signal.SIGKILL)
        with pytest.raises(WorkerDied) as ei:
            fut.result(timeout=30)
        assert ei.value.worker == 0
        assert str(ei.value).startswith("env worker 0")
        # The error is cached on the future (PR-8 Future semantics).
        assert fut.exception(timeout=0) is ei.value
        out = _retry_step(pool, 0, a)
        # Surviving worker's slice (envs 2..3): exactly one step applied.
        assert (out["episode_step"][2:] == pre[2:] + 1).all(), (
            pre, out["episode_step"],
        )
        # Respawned slice: fresh envs on their first step.
        assert (out["episode_step"][:2] == 1).all()
        # The OTHER buffer still works (only the awaited batch failed).
        assert pool.step(1, a).result(timeout=30)["obs"].shape[0] == 4
    finally:
        pool.close()


def test_step_with_retry_helper_heals_worker_death():
    pool = EnvPool(FakeEnv, num_processes=2, batch_size=4, num_batches=1,
                   restart_backoff=0.05, name="t-helper")
    try:
        a = np.zeros(4, np.int64)
        pool.step(0, a).result(timeout=30)
        os.kill(pool._procs[1].pid, signal.SIGKILL)
        out = step_with_retry(pool, 0, a, timeout=30.0)
        assert out["obs"].shape[0] == 4
    finally:
        pool.close()


def test_watchdog_reaps_sigstop_wedge():
    """A SIGSTOP'd worker with a step dispatched is indistinguishable from
    a dead one to waiters: the hung-step watchdog must reap + respawn it
    within its deadline, failing the batch typed (kind=wedge counted)."""
    from moolib_tpu.telemetry import global_telemetry

    pool = EnvPool(SlowEnv, num_processes=2, batch_size=2, num_batches=1,
                   watchdog_timeout=1.0, restart_backoff=0.05,
                   name="t-wedge")
    try:
        a = np.zeros(2, np.int64)
        pool.step(0, a).result(timeout=30)
        os.kill(pool._procs[0].pid, signal.SIGSTOP)
        t0 = time.monotonic()
        fut = pool.step(0, a)
        with pytest.raises(WorkerDied, match="watchdog"):
            fut.result(timeout=30)
        assert time.monotonic() - t0 < 1.0 + 3.0  # deadline + slack
        assert _retry_step(pool, 0, a)["obs"].shape[0] == 2
        reg = global_telemetry().registry
        assert reg.value("envpool_worker_deaths_total",
                         pool="t-wedge", kind="wedge") == 1
    finally:
        pool.close()


def test_restart_budget_degrades_to_permanent_down():
    """A crash-looping worker (its envs hard-kill the process on every
    step) exhausts the restart budget and degrades to a permanently-down
    slot: its slice is masked with terminal transitions and the pool
    keeps serving the surviving slices instead of spinning."""
    pool = EnvPool(CrashEnv, num_processes=2, batch_size=4, num_batches=1,
                   restart_limit=1, restart_window=60.0,
                   restart_backoff=0.05, name="t-budget")
    try:
        a = np.zeros(4, np.int64)
        deadline = time.monotonic() + 45
        while not pool.workers_down():
            assert time.monotonic() < deadline, "slot never went down"
            try:
                pool.step(0, a).result(timeout=30)
            except WorkerDied:
                time.sleep(0.05)
        assert pool.workers_down() == (0,)  # CrashEnv seed 1 lives in slot 0
        out = _retry_step(pool, 0, a)
        assert out["done"][:2].all(), out["done"]  # masked slice: terminal
        assert (out["episode_step"][2:] > 0).all()  # survivors still step
        assert pool.supervisor_stats()["down"] == (0,)
    finally:
        pool.close()


def test_poison_env_quarantined_worker_survives():
    """An env that raises on every step is quarantined inside its worker
    after poison_threshold consecutive failures — terminal row, reported
    per index — and the worker NEVER dies (no respawn churn)."""
    from moolib_tpu.telemetry import global_telemetry

    pool = EnvPool(PoisonEnv, num_processes=2, batch_size=4, num_batches=1,
                   poison_threshold=2, name="t-poison")
    try:
        a = np.ones(4, np.int64)
        deadline = time.monotonic() + 20
        while pool.quarantined() != (1,):
            assert time.monotonic() < deadline, "poison never quarantined"
            out = pool.step(0, a).result(timeout=30)
            time.sleep(0.01)
        out = pool.step(0, a).result(timeout=30)
        assert bool(out["done"][1]) and out["episode_step"][1] == 0
        assert out["episode_step"][0] > 0  # healthy envs keep advancing
        reg = global_telemetry().registry
        assert reg.value("envpool_quarantined_total", pool="t-poison") == 1
        assert reg.value("envpool_worker_deaths_total",
                         pool="t-poison", kind="exit") is None
    finally:
        pool.close()


def test_pipe_mode_supervision(monkeypatch):
    """The supervision contract holds on the pipe fallback data plane too
    (no native semaphores): kill -> typed failure -> respawn -> exactly-
    once retry."""
    from moolib_tpu.envpool import pool as pool_mod

    monkeypatch.setattr(pool_mod, "_get_native", lambda: None)
    pool = EnvPool(SlowEnv, num_processes=2, batch_size=4, num_batches=2,
                   restart_backoff=0.05, name="t-pipe")
    try:
        assert pool._ctrl is None  # really on the pipe plane
        a = np.zeros(4, np.int64)
        pre = np.array(
            pool.step(0, a).result(timeout=30)["episode_step"], copy=True
        )
        fut = pool.step(0, a)
        time.sleep(0.05)
        os.kill(pool._procs[0].pid, signal.SIGKILL)
        with pytest.raises(WorkerDied):
            fut.result(timeout=30)
        out = _retry_step(pool, 0, a)
        assert (out["episode_step"][2:] == pre[2:] + 1).all()
    finally:
        pool.close()


def test_close_bounded_and_idempotent_with_stuck_worker():
    """ISSUE-12 satellite: close() with a SIGSTOP-stuck worker and a step
    in flight returns within the close budget (kill escalation reaps
    stopped processes), is idempotent, and __del__ after close is a
    no-op. The shm segment is released (no deferred-release leak)."""
    pool = EnvPool(SlowEnv, num_processes=2, batch_size=2, num_batches=1,
                   close_timeout=2.0, name="t-close")
    shm_name = pool._shm.name
    pool.step(0, np.zeros(2, np.int64)).result(timeout=30)
    fut = pool.step(0, np.zeros(2, np.int64))
    os.kill(pool._procs[1].pid, signal.SIGSTOP)
    t0 = time.monotonic()
    pool.close()
    assert time.monotonic() - t0 < 6.0  # bounded, not 5s-per-proc sums
    # The in-flight future resolves (closed), never hangs.
    with pytest.raises(RuntimeError, match="closed"):
        fut.result(timeout=0)
    t0 = time.monotonic()
    pool.close()  # idempotent: immediate no-op
    assert time.monotonic() - t0 < 0.1
    pool.__del__()  # and safe after close
    # Segment really unlinked: re-attaching by name must fail.
    from multiprocessing import shared_memory as mp_shm

    with pytest.raises(FileNotFoundError):
        mp_shm.SharedMemory(name=shm_name)


def test_future_timeout_contract():
    """EnvStepperFuture.result/exception follow the PR-8 Future contract:
    negative / non-finite timeouts raise ValueError, timeout=0 is a
    non-blocking poll."""
    pool = EnvPool(SlowEnv, num_processes=1, batch_size=1, num_batches=1,
                   name="t-timeout")
    try:
        fut = pool.step(0, np.zeros(1, np.int64))
        for bad in (-1, -0.5, float("inf"), float("nan")):
            with pytest.raises(ValueError, match="timeout"):
                fut.result(bad)
            with pytest.raises(ValueError, match="timeout"):
                fut.exception(bad)
        # timeout=0 polls: the SlowEnv step is still in flight.
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            fut.result(timeout=0)
        with pytest.raises(TimeoutError):
            fut.exception(timeout=0)
        assert time.monotonic() - t0 < 0.25, "timeout=0 must not block"
        out = fut.result(timeout=30)
        assert fut.exception(timeout=0) is None
        assert fut.result(timeout=0) is out  # cached outcome
    finally:
        pool.close()


def test_abandoned_pool_is_collected_and_workers_reaped():
    """Review regression: the supervisor thread holds the pool only via a
    weakref, so a pool dropped WITHOUT close() is still garbage-collected
    — __del__ runs close() and the worker processes die (no permanent
    worker/shm leak from an abandoned pool)."""
    import gc
    import weakref as _weakref

    pool = EnvPool(FakeEnv, num_processes=1, batch_size=1, num_batches=1,
                   name="t-gc")
    if pool._ctrl is None:
        pool.close()
        pytest.skip("pipe mode's drain thread pins the pool (pre-existing)")
    pool.step(0, np.zeros(1, np.int64)).result(timeout=30)
    pid = pool._procs[0].pid
    wref = _weakref.ref(pool)
    del pool
    deadline = time.monotonic() + 10
    while wref() is not None and time.monotonic() < deadline:
        gc.collect()
        time.sleep(0.05)
    assert wref() is None, "abandoned pool never collected (leak)"
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            break  # worker reaped by __del__ -> close()
        time.sleep(0.05)
    else:
        raise AssertionError("abandoned pool's worker still alive")


@pytest.mark.parametrize("plane", ["native", "pipe"])
def test_envs_own_step_and_ready_idle_are_stamped_by_the_workers(
        plane, monkeypatch):
    """The workers stamp where their slice ends, so a batch's wall splits
    into the envs' own step (no shorter than the env's sleep) and the time
    the finished batch lay ready (no shorter than the caller's delay less
    that step), in both data-plane modes; the envpool ledger closes."""
    from moolib_tpu.envpool import pool as pool_mod
    from moolib_tpu.telemetry import global_telemetry, summarize_stepscope

    if plane == "pipe":
        monkeypatch.setattr(pool_mod, "_get_native", lambda: None)
    elif pool_mod._get_native() is None:
        pytest.skip("native data plane unavailable (pipe mode)")
    delay, turns = 0.6, 3

    def ledger():
        s = summarize_stepscope(global_telemetry().snapshot()).get(
            "envpool", {"wall_s": 0.0, "phases": {}}
        )
        return s["wall_s"], dict(s["phases"])

    reg = global_telemetry().registry
    hist = lambda: reg.snapshot()["envpool_env_step_seconds"]  # noqa: E731
    with EnvPool(SlowEnv, num_processes=2, batch_size=2, num_batches=2,
                 name=f"t-stamps-{plane}") as pool:
        assert (pool._ctrl is None) == (plane == "pipe")
        assert pool.step_times() == (0.0, 0.0)
        a = np.zeros(2, np.int64)
        wall0, phases0 = ledger()
        count0 = hist()["count"]
        for turn in range(turns):
            fut = pool.step(turn % 2, a)
            time.sleep(delay)
            fut.result(timeout=30)
            own, idle = pool.step_times()
            # One env a worker: the slice is one sleep of the env's.
            assert own >= (turn + 1) * SlowEnv.STEP_SECONDS
            assert idle >= (turn + 1) * delay - own - 0.01
        # Collected at once: the caller waits, nothing lies ready.
        pool.step(0, a).result(timeout=30)
        own2, idle2 = pool.step_times()
        assert own2 >= own + SlowEnv.STEP_SECONDS
        assert idle2 == idle
        wall1, phases1 = ledger()
    spent = {k: v - phases0.get(k, 0.0) for k, v in phases1.items()}
    assert sum(spent.values()) == pytest.approx(wall1 - wall0, rel=1e-6)
    # The ledger's `ready_idle` is the pool's own count of it, and
    # `batch_fill` stops where the workers stopped.
    assert spent["ready_idle"] == pytest.approx(idle2, rel=1e-6)
    assert spent["batch_fill"] + spent["env_wait"] == pytest.approx(
        wall1 - wall0 - idle2 - spent.get("staging", 0.0), rel=1e-6
    )
    # None of the three turns' 0.45 s of lying ready is in it (what a
    # collect does after its wait is, so a loaded host gets room).
    assert spent["batch_fill"] < own2 + delay
    assert hist()["count"] - count0 == turns + 1
