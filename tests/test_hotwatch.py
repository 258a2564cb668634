"""hotwatch: the dynamic mirror of the hotlint family.

The acceptance scenario rides here: a planted steady-state ``.item()``
is caught at runtime with the stack of the materialization site (the
static half lives in test_lint.py's hotlint fixtures). Plus the window
contracts: budgeted transfers pass, staged async copies are free,
``enabled=False`` patches nothing, compile counts must stay flat, and
counting is scoped to the window's thread (get_state-style reads on RPC
threads stay free).
"""

import concurrent.futures
import threading

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from moolib_tpu.testing import Hotwatch, HotwatchViolation  # noqa: E402
from moolib_tpu.testing.hotwatch import hotwatch_enabled  # noqa: E402


@pytest.fixture
def step():
    fn = jax.jit(lambda s: s + 1)
    fn(jnp.zeros((8,)))  # warm: compile + constant H2D outside windows
    return fn


def test_planted_item_caught_with_site_stack(step):
    """THE acceptance scenario: one steady-state `.item()` inside the
    window raises at the call site, naming this file in the stack."""
    s = step(jnp.zeros((8,)))
    with pytest.raises(HotwatchViolation) as ei:
        with Hotwatch(jits=[step], label="steady"):
            for _ in range(3):
                s = step(s)
                s.sum().item()  # the planted sync
    msg = str(ei.value)
    assert "steady" in msg
    assert "Materialization site" in msg
    assert "tests/test_hotwatch.py" in msg


def test_budgeted_transfers_pass_and_are_counted(step):
    """A window with d2h=N tolerates N synchronous reads (the budgeted-
    warmup shape) and reports the count."""
    s = step(jnp.zeros((8,)))
    with Hotwatch(d2h=2, jits=[step]) as hw:
        for _ in range(4):
            s = step(s)
        float(s.sum())
    assert hw.d2h == 1
    assert hw.compile_delta == 0


def test_staged_copy_is_free(step):
    """copy_to_host_async is the discipline the window enforces: staging
    counts as staged, never as a violation, and the later re-read of the
    fetched value is not a transfer."""
    s = step(jnp.zeros((8,)))
    with Hotwatch(jits=[step]) as hw:
        for _ in range(3):
            s = step(s)
            s.copy_to_host_async()
    assert hw.d2h == 0
    assert hw.staged == 3


def test_np_asarray_buffer_path_is_caught(step):
    """np.asarray bypasses the array's _value property via the buffer
    protocol; the wrapped module function still catches it."""
    s = step(jnp.zeros((8,)))
    with pytest.raises(HotwatchViolation):
        with Hotwatch(jits=[step]):
            s = step(s)
            np.asarray(s)


def test_disabled_window_patches_nothing(step):
    """enabled=False (and the MOOLIB_TPU_HOTWATCH=0 escape hatch) is a
    true no-op: the array class keeps its original descriptors and syncs
    inside the window are free."""
    from jax._src.array import ArrayImpl

    before_value = ArrayImpl._value
    before_stage = ArrayImpl.copy_to_host_async
    s = step(jnp.zeros((8,)))
    with Hotwatch(enabled=False, jits=[step]) as hw:
        assert ArrayImpl._value is before_value
        assert ArrayImpl.copy_to_host_async is before_stage
        s = step(s)
        s.sum().item()
    assert hw.d2h == 0
    assert ArrayImpl._value is before_value


def test_env_gate(monkeypatch):
    monkeypatch.setenv("MOOLIB_TPU_HOTWATCH", "0")
    assert not hotwatch_enabled()
    assert not Hotwatch().enabled
    monkeypatch.setenv("MOOLIB_TPU_HOTWATCH", "1")
    assert hotwatch_enabled(default=False)
    monkeypatch.delenv("MOOLIB_TPU_HOTWATCH")
    assert hotwatch_enabled()


def test_patches_restored_after_window(step):
    """Exit (clean or raising) restores every descriptor: reads outside
    any window are untouched."""
    from jax._src.array import ArrayImpl

    before = ArrayImpl._value
    s = step(jnp.zeros((8,)))
    with pytest.raises(HotwatchViolation):
        with Hotwatch(jits=[step]):
            s.sum().item()
    assert ArrayImpl._value is before
    assert float(step(s)[0]) == pytest.approx(2.0)


def test_compile_flatness_violation(step):
    """A new shape inside the window recompiles the step; the window
    raises on exit even with transfers budgeted away."""
    with pytest.raises(HotwatchViolation, match="compiled"):
        with Hotwatch(d2h=99, jits=[step]):
            step(jnp.zeros((16,)))  # new shape: retrace


def test_compile_budget_allows_declared_compiles(step):
    with Hotwatch(d2h=99, jits=[step], max_compiles=1) as hw:
        step(jnp.zeros((32,)))
    assert hw.compile_delta == 1


def test_off_thread_reads_are_free(step):
    """get_state-style full-model reads run on RPC/broadcast threads
    under their own lock; a step-loop window must not charge them."""
    s = step(jnp.zeros((8,)))
    errs = []
    with Hotwatch(jits=[step]) as hw:
        def reader():
            try:
                jax.device_get(s)
            except concurrent.futures.CancelledError as e:  # pragma: no cover
                errs.append(e)
                raise  # recorded for the assertion below, never swallowed
            except Exception as e:  # pragma: no cover - failure capture
                errs.append(e)
        t = threading.Thread(target=reader)
        t.start()
        t.join()
        s = step(s)
    assert not errs
    assert hw.d2h == 0


def test_h2d_disallow_catches_unstaged_upload(step):
    """h2d=0 enters the native transfer guard: feeding a numpy array to
    the jitted step inside the window aborts (the per-step upload the
    static rules can't always see)."""
    with pytest.raises(Exception, match="[Dd]isallow"):
        with Hotwatch(d2h=99, h2d=0):
            step(np.zeros((8,), dtype=np.float32))


def test_violation_raised_inside_user_code_wins_over_exit_checks(step):
    """An exception inside the block propagates; the exit-time compile
    check must not mask it."""
    with pytest.raises(ValueError, match="user"):
        with Hotwatch(jits=[step]):
            step(jnp.zeros((64,)))  # would be a compile violation
            raise ValueError("user error")


# -- e2e wiring: the real learner machinery under a window --------------------


def test_learner_e2e_steady_state_zero_transfers():
    """The real fused IMPALA train step (donating, metrics left on
    device) runs a steady-state window with ZERO synchronous D2H, zero
    H2D, and flat compile counts — the contract the examples' learn
    path is built to honor and the bench row records on every PR."""
    import optax

    from moolib_tpu.learner import (ImpalaConfig, make_impala_train_step,
                                    make_train_state)
    from moolib_tpu.models import A2CNet

    t_dim, b_dim, f_dim, a_dim = 4, 4, 5, 3
    net = A2CNet(num_actions=a_dim, hidden_sizes=(16,))
    params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, f_dim)),
                      jnp.zeros((1, 1), bool), ())
    state = make_train_state(params, optax.sgd(1e-3))
    train_step = make_impala_train_step(
        net.apply, optax.sgd(1e-3), ImpalaConfig(), donate=True
    )
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    batch = {
        "obs": jax.random.normal(ks[0], (t_dim + 1, b_dim, f_dim),
                                 jnp.float32),
        "done": jax.random.bernoulli(ks[1], 0.1, (t_dim + 1, b_dim)),
        "rewards": jax.random.normal(ks[2], (t_dim + 1, b_dim),
                                     jnp.float32),
        "actions": jax.random.randint(ks[3], (t_dim, b_dim), 0, a_dim),
        "behavior_logits": jnp.zeros((t_dim, b_dim, a_dim), jnp.float32),
        "core_state": (),
    }
    for _ in range(2):  # warmup: compile + first-touch
        state, metrics = train_step(state, batch)
    jax.block_until_ready(state)

    with Hotwatch(jits=[train_step], d2h=0, h2d=0, max_compiles=0,
                  label="learner-e2e", enabled=True) as hw:
        for _ in range(10):
            state, metrics = train_step(state, batch)
    jax.block_until_ready(state)
    assert hw.d2h == 0
    assert hw.compile_delta == 0
    # The window didn't neuter the pipeline: metrics are real.
    assert float(metrics["total_loss"]) == float(metrics["total_loss"])


def test_example_actor_loop_designed_syncs_exactly_budgeted():
    """The examples' actor boundary (a2c.py / vtrace experiment): per
    step, exactly TWO host materializations are the design — the action
    feed and the behavior logits riding the unroll buffer (both carry
    `# hotlint: sync` suppressions in the source). A window budgeted for
    exactly 2*N passes and counts exactly 2*N; one stray extra sync
    would blow the budget and raise."""
    from moolib_tpu.learner import make_act_step
    from moolib_tpu.models import A2CNet

    b_dim, f_dim, a_dim = 4, 5, 3
    net = A2CNet(num_actions=a_dim, hidden_sizes=(16,))
    params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 1, f_dim)),
                      jnp.zeros((1, 1), bool), ())
    act = make_act_step(net.apply)
    rng = jax.random.PRNGKey(1)
    obs = jnp.zeros((b_dim, f_dim))
    done = jnp.zeros((b_dim,), bool)
    a, logits, core = act(params, rng, obs, done, ())  # warm
    np.asarray(a), np.asarray(logits)

    n = 5
    with Hotwatch(jits=[act], d2h=2 * n, max_compiles=0,
                  label="actor-loop", enabled=True) as hw:
        for _ in range(n):
            rng, sub = jax.random.split(rng)
            a, logits, core = act(params, sub, obs, done, core)
            host_a = np.asarray(a)       # designed: feeds the envs NOW
            host_l = np.asarray(logits)  # designed: rides the unroll buf
    assert hw.d2h == 2 * n
    assert hw.compile_delta == 0
    assert host_a.shape == (b_dim,)
    assert host_l.shape == (b_dim, a_dim)
