"""Integration tests for the examples layer.

Reference strategy: test/integration/test_a2c.py trains the real A2C example
and asserts learning-curve properties (return >100 for >=50% of the last
logs, entropy bounds). Same bar here, on the CPU backend the whole suite
runs under (conftest.py).
"""

import dataclasses

import numpy as np
import pytest

import moolib_tpu
from moolib_tpu.examples.a2c import A2CConfig, train as a2c_train
from moolib_tpu.examples.vtrace import experiment
from moolib_tpu.examples.vtrace.experiment import (
    VtraceConfig,
    train as vtrace_train,
)


def _quiet(*a, **k):
    pass


@pytest.mark.integration
@pytest.mark.slow  # learning bar is wall-clock-paced (async accumulator
# updates race env steps), so host load — not code — decides the outcome
# when it lands inside the tier-1 window; verified flaky at HEAD too.
def test_a2c_cartpole_learns():
    cfg = A2CConfig(seed=0, total_steps=60_000, log_interval_steps=2_000)
    logs = a2c_train(cfg, log_fn=_quiet)
    assert len(logs) >= 20
    tail = [r["mean_episode_return"] for r in logs[-10:]]
    # Learning bar (reference: test/integration/test_a2c.py:16-36).
    assert sum(r > 100 for r in tail) >= 5, f"tail returns {tail}"
    entropies = [r["entropy"] for r in logs[-10:]]
    assert all(0.05 < e < 0.69 for e in entropies), entropies
    assert logs[-1]["updates"] > 100


def test_vtrace_experiment_runs_and_checkpoints(tmp_path):
    cfg = VtraceConfig(
        env="cartpole",
        total_steps=6_000,
        actor_batch_size=8,
        learn_batch_size=8,
        virtual_batch_size=8,
        num_actor_processes=2,
        unroll_length=10,
        log_interval_steps=2_000,
        savedir=str(tmp_path),
        checkpoint_interval=0.0,  # save at every opportunity
        checkpoint_history_interval=None,
        stats_interval=0.2,
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert len(logs) == 3
    assert logs[-1]["updates"] > 10
    assert np.isfinite(logs[-1]["total_loss"])
    # tsv + metadata + checkpoint written
    assert (tmp_path / "logs.tsv").exists()
    assert (tmp_path / "metadata.json").exists()
    assert (tmp_path / "checkpoint.ckpt").exists()
    # global stats eventually include our own env steps
    assert logs[-1]["global_env_steps"] > 0

    # Resume: checkpoint holder wins leader election and model_version
    # carries over (reference: experiment.py:316-322).
    vers = [r["model_version"] for r in logs]
    cfg2 = VtraceConfig(**{**cfg.__dict__, "total_steps": 2_000})
    logs2 = vtrace_train(cfg2, log_fn=_quiet)
    assert logs2[0]["model_version"] >= vers[-1]


def test_vtrace_synthetic_pixels_smoke(tmp_path):
    """Pixel pipeline end-to-end on the synthetic Atari-shaped env with the
    deep ResNet — a handful of updates, loss finite."""
    cfg = VtraceConfig(
        env="synthetic",
        num_actions=4,
        episode_length=40,
        total_steps=640,
        actor_batch_size=4,
        learn_batch_size=4,
        virtual_batch_size=4,
        num_actor_processes=2,
        num_actor_batches=2,
        unroll_length=4,
        log_interval_steps=320,
        stats_interval=1e9,
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])


def test_vtrace_lstm_smoke():
    """LSTM core_state ([B, H]) must batch correctly alongside [T, B, ...]
    unroll leaves (per-key Batcher dims)."""
    cfg = VtraceConfig(
        env="cartpole",
        use_lstm=True,
        total_steps=2_000,
        actor_batch_size=4,
        learn_batch_size=8,  # two unrolls per learn batch: exercises the cat
        virtual_batch_size=8,
        num_actor_processes=2,
        unroll_length=5,
        log_interval_steps=1_000,
        stats_interval=1e9,
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])


def test_vtrace_transformer_smoke():
    """Transformer agent (long-context family) through the full vtrace loop."""
    cfg = VtraceConfig(
        env="cartpole",
        model="transformer",
        total_steps=2_000,
        actor_batch_size=4,
        learn_batch_size=8,
        virtual_batch_size=8,
        num_actor_processes=2,
        unroll_length=5,
        log_interval_steps=1_000,
        stats_interval=1e9,
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])


def test_vtrace_nethack_smoke():
    """Benchmark config 5's stack end to end: dict observations (glyphs +
    blstats) through EnvPool, two-stage batching, NetHackNet LSTM, V-trace."""
    cfg = VtraceConfig(
        env="nethack",
        num_actions=23,
        use_lstm=True,
        total_steps=1_500,
        actor_batch_size=4,
        learn_batch_size=4,
        virtual_batch_size=4,
        num_actor_processes=2,
        unroll_length=5,
        log_interval_steps=500,
        stats_interval=1e9,
        compute_dtype="float32",
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])


def test_vtrace_procgen_smoke():
    """Benchmark config 4's stack: 64x64x3 ProcGen-shaped pixels through the
    ResNet encoder (synthetic stand-in when procgen isn't installed)."""
    cfg = VtraceConfig(
        env="procgen",
        num_actions=15,
        total_steps=1_000,
        actor_batch_size=4,
        learn_batch_size=4,
        virtual_batch_size=4,
        num_actor_processes=2,
        unroll_length=5,
        log_interval_steps=500,
        stats_interval=1e9,
        compute_dtype="float32",
        seed=0,
    )
    logs = vtrace_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])


def test_a2c_pixel_smoke():
    """A2C with the ResNet torso on Atari-shaped pixels (benchmark config 2:
    A2C on Atari — synthetic stand-in in CI)."""
    from moolib_tpu.examples.a2c import A2CConfig, train as a2c_train

    cfg = A2CConfig(
        env="synthetic",
        num_actions=6,
        total_steps=600,
        unroll_length=5,
        batch_size=2,
        num_processes=2,
        log_interval_steps=300,
        seed=0,
    )
    logs = a2c_train(cfg, log_fn=_quiet)
    assert logs and logs[-1]["updates"] >= 1
    assert np.isfinite(logs[-1]["total_loss"])
    # The logged rows also land in the scrapeable registry
    # (publish_metrics bridge): a live __telemetry scrape of a training
    # process shows its progress.
    from moolib_tpu.telemetry import global_telemetry

    reg = global_telemetry().registry
    assert reg.value("train_total_loss", example="a2c") == pytest.approx(
        logs[-1]["total_loss"]
    )
    assert reg.value("train_updates", example="a2c") == logs[-1]["updates"]


@pytest.mark.integration
@pytest.mark.slow  # same wall-clock pacing caveat as the a2c learning bar
def test_remote_actors_learner():
    """SEED-style split: two thin actor loops feed a central learner over
    RPC — policy served via define(batch_size=, pad=True) inference
    batching, unrolls shipped into a define_queue (the reference's
    EnvStepper/central-inference topology)."""
    import threading

    from moolib_tpu.examples.remote_actors import (
        RemoteConfig,
        run_actor,
        run_learner,
    )

    cfg = RemoteConfig(
        env="cartpole",
        actor_batch_size=2,
        num_env_processes=2,
        unroll_length=5,
        infer_batch_size=4,
        learn_batch_size=4,
        total_updates=20,   # exit as soon as the work is done...
        max_seconds=120,    # ...with a generous safety cap
        log_interval=0.5,
    )
    addr_box = {}
    addr_ready = threading.Event()

    def on_ready(addr):
        addr_box["addr"] = addr
        addr_ready.set()

    logs_box = {}

    def learner():
        logs_box["logs"] = run_learner(
            cfg, log_fn=_quiet, ready_fn=on_ready
        )

    lt = threading.Thread(target=learner)
    lt.start()
    assert addr_ready.wait(30), "learner never reported its address"

    frames = []
    actors = [
        threading.Thread(
            target=lambda: frames.append(
                run_actor(cfg, addr_box["addr"], max_seconds=60)
            )
        )
        for _ in range(2)
    ]
    for t in actors:
        t.start()
    lt.join(timeout=150)
    assert not lt.is_alive(), "learner never reached total_updates"
    for t in actors:  # actors break cleanly once the learner is gone
        t.join(timeout=90)
        assert not t.is_alive()
    assert sum(frames) > 0
    rows = logs_box["logs"]
    assert rows and rows[-1]["updates"] >= 1
    # publish_metrics bridge: the learner's final flush leaves the
    # registry at least as fresh as the last logged row (the loop exit —
    # total_updates or max_seconds — may postdate the last 0.5s log tick).
    from moolib_tpu.telemetry import global_telemetry

    tele_updates = global_telemetry().registry.value(
        "train_updates", example="remote_actors"
    )
    assert tele_updates is not None
    assert tele_updates >= rows[-1]["updates"]
    assert np.isfinite(rows[-1]["total_loss"])


def test_remote_actor_inference_samples_fresh_keys():
    """Regression for the served-inference PRNG discipline: under a
    FIXED model, successive infer calls must draw with fresh subkeys
    (sampled actions vary across steps — a reused key would freeze
    them), and the same seed must replay the identical action sequence
    bit-for-bit (the paritywatch contract)."""
    import threading

    import jax
    import jax.numpy as jnp

    from moolib_tpu.examples.remote_actors import make_infer_fn
    from moolib_tpu.models import A2CNet

    net = A2CNet(num_actions=4, hidden_sizes=(8,))
    params = net.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1, 4), jnp.float32),
        jnp.zeros((1, 1), bool), net.initial_state(1),
    )
    obs = np.random.default_rng(0).standard_normal((1, 3, 4)).astype(
        np.float32
    )
    done = np.zeros((1, 3), bool)

    infer = make_infer_fn(net.apply, lambda: params, 1, threading.Lock())
    steps = [infer(obs, done)[0] for _ in range(8)]
    assert any(
        not np.array_equal(steps[0], s) for s in steps[1:]
    ), "sampled actions frozen across steps — the infer key is not advancing"

    # Replay parity: a fresh factory with the same seed and the same
    # params walks the same key chain, so the whole action sequence
    # (and the logits) must match exactly.
    replay = make_infer_fn(net.apply, lambda: params, 1, threading.Lock())
    for step, (a, logits) in zip(
        steps, (replay(obs, done) for _ in range(8))
    ):
        np.testing.assert_array_equal(step, a)
    # Different seed, different draws (with overwhelming probability
    # over 24 categorical samples from a near-uniform fresh policy).
    other = make_infer_fn(net.apply, lambda: params, 2, threading.Lock())
    others = [other(obs, done)[0] for _ in range(8)]
    assert any(
        not np.array_equal(a, b) for a, b in zip(steps, others)
    )


# -- the vtrace loop's acting turn: one act call in flight -------------------
#
# train() driven with a deterministic in-process pool in EnvPool's place and
# recording wrappers around the act step, the gradient step and
# EnvBatchState: the order of a turn, the sameness of what it hands the
# learner, and its exits.


class _FakePool:
    """EnvPool's surface as train() uses it, in process and deterministic:
    env b of batch i is a counter that the action it is given moves. Like
    the pool's shared memory, a batch's buffers are written in place, and
    at once when the batch is submitted: a frame that was not consumed
    before its batch was stepped again reads as the next one."""

    def __init__(self, env_fn=None, *, batch_size, num_batches,
                 num_processes=0, action_dtype=np.int64, events=None,
                 fail_at_wait=None, fail_with=None):
        B = batch_size
        self.events = [] if events is None else events
        self.fail_at_wait, self.fail_with = fail_at_wait, fail_with
        self.waits = 0
        self.last_waited = None  # the batch whose frame was handed out last
        self.closed = False
        self.pending = [False] * num_batches
        self.died = [False] * num_batches
        self.submitted = [[] for _ in range(num_batches)]
        self.x = [np.arange(B, dtype=np.int64) * 7919 + 104729 * (i + 1)
                  for i in range(num_batches)]
        self.length = [5 + (np.arange(B) + i) % 4 for i in range(num_batches)]
        self.ep_step = [np.zeros(B, np.int64) for _ in range(num_batches)]
        self.ep_ret = [np.zeros(B, np.float32) for _ in range(num_batches)]
        # obs at a multiple of 64, which the CPU backend takes without a
        # copy, as it may the pool's views: a frame staged by reference to
        # it reads as the next one.
        raw = [np.zeros(B * 4 + 16, np.float32) for _ in range(num_batches)]
        self.out = [
            {"obs": r[(-r.ctypes.data % 64) // 4:][:B * 4].reshape(B, 4),
             "reward": np.zeros(B, np.float32),
             "done": np.zeros(B, bool),
             "episode_step": np.zeros(B, np.int64),
             "episode_return": np.zeros(B, np.float32)}
            for r in raw
        ]

    def step(self, i, actions):
        actions = np.array(actions, np.int64)
        if self.died[i]:
            # A retry after WorkerDied: the same action, stepped once.
            assert np.array_equal(actions, self.submitted[i][-1])
            self.died[i] = False
            self.events.append(("retry", i))
            return _FakeFuture(self, i)
        assert not self.pending[i], f"batch {i} submitted twice"
        self.pending[i] = True
        self.submitted[i].append(actions)
        self.events.append(("submit", i))
        x = (self.x[i] * 1103515245 + 12345 + actions * 40503) % (2 ** 31)
        self.x[i] = x
        out = self.out[i]
        for k in range(4):
            out["obs"][:, k] = ((x >> (5 * k)) & 1023) / 1023.0
        out["reward"][:] = (x >> 3) % 7 - 3
        self.ep_step[i] += 1
        self.ep_ret[i] += out["reward"]
        done = self.ep_step[i] >= self.length[i]
        out["done"][:] = done
        out["episode_step"][:] = self.ep_step[i]
        out["episode_return"][:] = self.ep_ret[i]
        self.ep_step[i][done] = 0
        self.ep_ret[i][done] = 0.0
        return _FakeFuture(self, i)

    def step_times(self):
        return 0.0, 0.0

    def close(self):
        self.closed = True


class _FakeFuture:
    def __init__(self, pool, i):
        self.pool, self.i = pool, i

    def result(self, timeout=None):
        pool, i = self.pool, self.i
        assert pool.pending[i] and not pool.closed
        pool.waits += 1
        if pool.waits == pool.fail_at_wait:
            if pool.fail_with is moolib_tpu.WorkerDied:
                pool.died[i] = True
            raise pool.fail_with(f"wait {pool.waits} of the fake pool")
        pool.events.append(("wait", i))
        pool.last_waited = i
        pool.pending[i] = False
        return pool.out[i]


class _TurnRecord:
    """What one train() run did, as the fakes saw it."""

    def __init__(self):
        self.events = []  # (what, batch) in the order they happened
        self.keeps = []  # start_unroll's answers, in the order asked
        self.act_params = []  # the params each act call was given
        self.learn = []  # (learn batch on the host, total_loss)
        self.lines = []  # log_fn's rows
        self.pool = None

    def of(self, *kinds):
        return [e for e in self.events if e[0] in kinds]

    def logged_env_steps(self):
        return [int(line.split("steps", 1)[1].split()[0])
                for line in self.lines]


def _vtrace_fake_cfg(**kw):
    # A learn batch of 7 columns over windows of 3: windows end one slab
    # and begin the next, and 7 shares no factor with the 8 CPU devices,
    # so the steps run without a mesh, as the test's own copies do.
    return VtraceConfig(**dict(dict(
        env="cartpole", actor_batch_size=3, learn_batch_size=7,
        virtual_batch_size=7, unroll_length=4, num_actor_batches=2,
        num_actor_processes=0, learning_rate=0.0, log_interval_steps=3,
        stats_interval=1e9, total_steps=10 ** 12, max_seconds=120.0, seed=3,
    ), **kw))


def _run_vtrace_on_fakes(monkeypatch, cfg, *, until_updates=None,
                         fail_at_wait=None, fail_with=None):
    """train(cfg) on the fakes. With ``until_updates`` the run is ended
    from log_fn, as the benchmark ends it, once that many updates have
    applied. Returns the record; the exception the pool was told to fail
    with, and train() raised, is on it as ``raised``."""
    import jax

    import moolib_tpu.learner as learner
    from moolib_tpu.examples.common import EnvBatchState

    rec = _TurnRecord()

    def make_pool(env_fn, **kw):
        rec.pool = _FakePool(
            env_fn, events=rec.events, fail_at_wait=fail_at_wait,
            fail_with=fail_with, **kw,
        )
        return rec.pool

    class RecordingBatchState(EnvBatchState):
        made = 0

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.index = RecordingBatchState.made
            RecordingBatchState.made += 1

        def start_unroll(self, keep=True):
            rec.keeps.append(keep)
            super().start_unroll(keep)

        def record_action(self, *a, **kw):
            rec.events.append(("sync", self.index))
            super().record_action(*a, **kw)

    make_act, make_grad = learner.make_act_step, learner.make_grad_step

    def recording_act_step(*a, **kw):
        act = make_act(*a, **kw)

        def recording_act(params, *rest):
            # The batch is the one whose envs were waited for last.
            rec.events.append(("dispatch", rec.pool.last_waited))
            rec.act_params.append(params)
            return act(params, *rest)

        return recording_act

    def recording_grad_step(*a, **kw):
        grad = make_grad(*a, **kw)

        def recording_grad(params, batch):
            grads, metrics = grad(params, batch)
            # Copied: on the CPU backend the device's arrays can alias the
            # slab, and a recycled slab is written again.
            rec.learn.append((jax.tree_util.tree_map(np.array, batch),
                              float(metrics["total_loss"])))
            return grads, metrics

        return recording_grad

    def log_fn(line):
        rec.lines.append(line)
        if until_updates is not None:
            updates = float(line.rsplit("updates", 1)[1])
            if updates >= until_updates:
                cfg.max_seconds = 0.0  # the loop reads it every turn

    monkeypatch.setattr(moolib_tpu, "EnvPool", make_pool)
    monkeypatch.setattr(experiment, "EnvBatchState", RecordingBatchState)
    monkeypatch.setattr(learner, "make_act_step", recording_act_step)
    monkeypatch.setattr(learner, "make_grad_step", recording_grad_step)
    rec.raised = None
    if fail_with in (None, moolib_tpu.WorkerDied):  # the loop retries those
        vtrace_train(cfg, log_fn=log_fn)
    else:
        with pytest.raises(fail_with) as raised:
            vtrace_train(cfg, log_fn=log_fn)
        rec.raised = raised.value
    return rec


def _act_calls_total():
    from moolib_tpu.telemetry import global_telemetry

    reg = global_telemetry().registry
    return {
        overlapped: reg.counter(
            "vtrace_act_calls_total", overlapped=overlapped
        ).value
        for overlapped in ("0", "1")
    }


@pytest.mark.parametrize("num_actor_batches", [1, 2, 3])
def test_vtrace_turn_keeps_one_act_call_in_flight(monkeypatch,
                                                  num_actor_batches):
    """Each batch goes wait, dispatch, host_sync, submit; with several
    batches a call is finished after the next batch's dispatch, with one
    it is finished at once; the counter says which."""
    n = num_actor_batches
    before = _act_calls_total()
    rec = _run_vtrace_on_fakes(
        monkeypatch, _vtrace_fake_cfg(
            num_actor_batches=n, total_steps=3 * 40,
            log_interval_steps=3 * n * 5,
        ),
    )
    assert rec.raised is None
    # A row every five turns, as when every turn ended with its batches
    # submitted: the call in flight counts towards the row that is due,
    # and not yet among the row's env steps.
    in_flight = 3 if n > 1 else 0
    assert rec.logged_env_steps()[:2] == [
        3 * n * 5 * row - in_flight for row in (1, 2)
    ]
    counted = {k: v - before[k] for k, v in _act_calls_total().items()}
    syncs = rec.of("sync")
    assert len(syncs) >= 40
    for i in range(n):
        own = [what for what, batch in rec.events if batch == i]
        cycle = ["submit", "wait", "dispatch", "sync"]
        assert own == (cycle * len(own))[:len(own)], (i, own[:12])
    order = rec.of("dispatch", "sync")
    if n == 1:
        assert order == [("dispatch", 0), ("sync", 0)] * len(syncs)
        assert counted == {"0": len(syncs), "1": 0}
    else:
        # A call is finished right after the dispatch of the batch that
        # follows it, and before any other dispatch.
        for at, (what, batch) in enumerate(order):
            if what == "sync":
                assert order[at - 1] == ("dispatch", (batch + 1) % n), at
        assert [b for _, b in syncs] == [k % n for k in range(len(syncs))]
        assert counted == {"0": 0, "1": len(syncs)}
    # A turn's act calls all read the parameters the turn began with: they
    # are dispatched before its apply step.
    params = rec.act_params
    for turn in range(len(params) // n):
        assert all(p is params[turn * n] for p in params[turn * n:][:n])


def _sequential_vtrace_reference(cfg, keeps, count):
    """The plain turn, a batch at a time with nothing in flight: wait,
    observe, act, block, read, record, submit. Returns the first ``count``
    learn batches and their losses at the initial parameters."""
    import jax
    import jax.numpy as jnp

    from moolib_tpu.examples.common import EnvBatchState
    from moolib_tpu.learner import (
        ImpalaConfig, make_act_step, make_grad_step,
    )
    from moolib_tpu.ops.batcher import LearnSlabs

    net = experiment._make_model(cfg)
    rng = jax.random.PRNGKey(cfg.seed)
    rng, init_rng = jax.random.split(rng)
    params = net.init(
        init_rng, jnp.zeros((1, 1, 4), jnp.float32),
        jnp.zeros((1, 1), bool), net.initial_state(1),
    )
    act = make_act_step(net.apply)
    grad = make_grad_step(
        net.apply,
        config=ImpalaConfig(
            discounting=cfg.discounting, baseline_cost=cfg.baseline_cost,
            entropy_cost=cfg.entropy_cost, reward_clip=cfg.reward_clip,
        ),
        mesh=None, grad_scale=float(cfg.learn_batch_size),
    )
    n, B = cfg.num_actor_batches, cfg.actor_batch_size
    pool = _FakePool(batch_size=B, num_batches=n)
    slabs = LearnSlabs(cfg.unroll_length, cfg.learn_batch_size)
    states = [
        EnvBatchState(cfg.unroll_length, net.initial_state(B), slabs=slabs)
        for _ in range(n)
    ]
    actions = [np.zeros(B, np.int64) for _ in range(n)]
    futures = [pool.step(i, actions[i]) for i in range(n)]
    keeps = iter(keeps)
    learn = []
    while len(learn) < count:
        for i in range(n):
            out = futures[i].result()
            if states[i].observe(out):
                states[i].start_unroll(next(keeps))
            rng, act_rng = jax.random.split(rng)
            a, logits, core = act(
                params, act_rng, jnp.asarray(out["obs"]),
                jnp.asarray(out["done"]), states[i].core_state,
            )
            a = np.asarray(a)
            states[i].record_action(a, np.asarray(logits), core)
            actions[i][:] = a
            futures[i] = pool.step(i, actions[i])
        while not slabs.empty():
            batch = jax.tree_util.tree_map(np.array, slabs.get().batch)
            _, metrics = grad(params, batch)
            learn.append((batch, float(metrics["total_loss"])))
    return learn[:count]


@pytest.mark.parametrize("num_actor_batches,use_lstm,learn_batch_size", [
    (1, False, 7), (2, False, 7), (2, True, 7), (3, True, 7),
    (2, False, 9),  # whole windows: three actor unrolls to a learn batch
])
def test_vtrace_learn_batches_are_the_sequential_turns(
        monkeypatch, num_actor_batches, use_lstm, learn_batch_size):
    """With the learning rate at 0 the parameters never move, so the run is
    a function of its seed: its learn batches and their losses are those
    of the plain sequential turn, bit for bit, given the same answers to
    ``start_unroll`` (the Accumulator connects when it connects).

    ``train()`` hands its slabs the frame it staged for the act call, and
    the learn batch's observation is put together on the device; the
    sequential turn hands over the env output alone, and its slabs copy
    every frame on the host. The fake pool writes a batch's next frame
    over its buffers when the batch is submitted, as a worker does."""
    import math

    import jax

    from moolib_tpu.ops import batcher
    from moolib_tpu.telemetry import global_telemetry

    cfg = _vtrace_fake_cfg(
        num_actor_batches=num_actor_batches, use_lstm=use_lstm,
        learn_batch_size=learn_batch_size,
        virtual_batch_size=learn_batch_size, log_interval_steps=96,
    )
    counters = {
        name: global_telemetry().registry.counter(
            f"learn_slab_{name}_total", slabs="learn_slabs"
        )
        for name in ("batches", "device_obs_batches")
    }
    before = {name: c.value for name, c in counters.items()}
    batcher._assemble_obs.clear_cache()
    rec = _run_vtrace_on_fakes(monkeypatch, cfg, until_updates=3)
    assert rec.raised is None
    assert len(rec.learn) >= 3
    # Every learn batch train() took was assembled on the device, by a
    # program traced once for each way its windows of 3 columns are cut.
    staged = counters["device_obs_batches"].value - before["device_obs_batches"]
    assert staged == len(rec.learn)
    assert counters["batches"].value - before["batches"] >= staged
    assert batcher._assemble_obs._cache_size() == (
        math.lcm(3, learn_batch_size) // learn_batch_size
    )
    monkeypatch.undo()
    reference = _sequential_vtrace_reference(cfg, rec.keeps, 3)
    assert counters["device_obs_batches"].value - before[
        "device_obs_batches"] == staged  # the reference went the host's way
    for (batch, loss), (ref_batch, ref_loss) in zip(rec.learn, reference):
        for name in ("obs", "done", "rewards", "actions", "behavior_logits",
                     "core_state"):
            got = jax.tree_util.tree_leaves(batch[name])
            want = jax.tree_util.tree_leaves(ref_batch[name])
            assert len(got) == len(want), name
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w, err_msg=name)
        assert np.isfinite(loss) and loss == ref_loss


@pytest.mark.parametrize("exit_by", ["total_steps", "exception",
                                     "worker_died"])
def test_vtrace_exit_with_an_act_call_in_flight(monkeypatch, exit_by):
    """However the loop ends, no batch is submitted twice (the fake pool
    asserts it), `env_steps` counts the batches submitted, and a batch
    retried after WorkerDied is retried with its last submitted actions."""
    B, n = 3, 2
    fail = {
        "total_steps": {},
        # The 41st wait opens a turn, batch 1's call in flight over it.
        "exception": dict(fail_at_wait=41, fail_with=RuntimeError),
        "worker_died": dict(fail_at_wait=24, fail_with=moolib_tpu.WorkerDied),
    }[exit_by]
    rec = _run_vtrace_on_fakes(
        monkeypatch, _vtrace_fake_cfg(total_steps=B * 61), **fail
    )
    if exit_by == "exception":
        assert "wait 41" in str(rec.raised)
    else:
        assert rec.raised is None
    assert rec.pool.closed
    assert ("retry", 1) in rec.events or exit_by != "worker_died"
    submits = len(rec.of("submit")) - n  # the first, before any act call
    assert submits == len(rec.of("sync"))
    assert rec.logged_env_steps()[-1] == submits * B
    if exit_by != "exception":
        assert submits * B >= B * 61
    # One call was dispatched and never read: dropped, not submitted.
    assert len(rec.of("dispatch")) == submits + 1


@pytest.mark.parametrize("base,item,want", [
    ({}, "total_steps=123", {"total_steps": 123}),
    ({}, "learning_rate=1e-3", {"learning_rate": 0.001}),
    ({}, "use_lstm=true", {"use_lstm": True}),
    ({"use_lstm": True}, "use_lstm=0", {"use_lstm": False}),
    ({}, "savedir=/tmp/run=1", {"savedir": "/tmp/run=1"}),  # Optional, None
    ({}, "learn-batch-size=64", {"learn_batch_size": 64}),
    ({}, "total_steps", "is not key=value"),
    ({}, "no_such_key=1", "unknown config key 'no_such_key'"),
    # no such field: sparse blocks are DecoderLM's, described by lm_config
    ({}, "transformer_mlp=moe", "unknown config key 'transformer_mlp'"),
])
def test_vtrace_overrides_onto_the_config(base, item, want):
    """``key=value`` items keep the field's type; what is no field, or no
    ``key=value``, exits and says which."""
    cfg = VtraceConfig(**base)
    if isinstance(want, str):
        with pytest.raises(SystemExit, match=want):
            experiment._apply_overrides(cfg, ["seed=3", item])
        return
    got = experiment._apply_overrides(cfg, ["seed=3", item])
    expected = dataclasses.replace(cfg, seed=3, **want)
    assert got == expected
    for key, value in want.items():
        assert type(getattr(got, key)) is type(value)


@pytest.mark.parametrize("env,model,want", [
    ("cartpole", "auto", "A2CNet"),
    ("nethack", "auto", "NetHackNet"),
    ("synthetic", "auto", "ImpalaNet"),
    ("synthetic", "no_such_model", ValueError),
])
def test_vtrace_model_by_name_and_by_environment(env, model, want):
    cfg = VtraceConfig(env=env, model=model, num_actions=5)
    if want is ValueError:
        with pytest.raises(ValueError, match="unknown model 'no_such_model'"):
            experiment._make_model(cfg)
        return
    net = experiment._make_model(cfg)
    assert type(net).__name__ == want
    assert net.num_actions == (2 if env == "cartpole" else 5)
