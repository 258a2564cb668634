"""The in-place learn-batch assembler (``LearnSlabs`` + ``EnvBatchState``)
against the path it replaced, kept here as the plain reference: frame lists
stacked into an unroll, unrolls concatenated by ``Batcher.cat``. Same
frames, same learn batches, bit for bit, whether the slab copies a frame on
the host or keeps the device array staged for its act call; then the reuse
guard, the drop, the counters, and ``EnvBatchState``'s contract as a2c and
remote_actors use it."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.examples.common import EnvBatchState, obs_from_env_out
from moolib_tpu.ops import batcher as batcher_module
from moolib_tpu.ops.batcher import Batcher, LearnSlabs, stage_frame
from moolib_tpu.telemetry import global_telemetry
from moolib_tpu.utils import nest

T = 5
NUM_ACTIONS = 3


class _StackingBatchState:
    """``EnvBatchState`` as it was before the slabs: every frame copied into
    a list, the lists stacked when an unroll completes."""

    def __init__(self, unroll_length, initial_core_state):
        self.T = unroll_length
        self.core_state = initial_core_state
        self._unroll_start_state = initial_core_state
        self._frames, self._actions, self._logits = [], [], []

    def observe(self, env_out):
        self._frames.append({
            "obs": nest.map_structure(np.array, obs_from_env_out(env_out)),
            "done": np.array(env_out["done"]),
            "rewards": np.asarray(env_out["reward"], np.float32).copy(),
        })
        if len(self._frames) < self.T + 1:
            return None
        unroll = {
            "obs": nest.map_structure(
                lambda *xs: np.stack(xs), *[f["obs"] for f in self._frames]
            ),
            "done": np.stack([f["done"] for f in self._frames]),
            "rewards": np.stack([f["rewards"] for f in self._frames]),
            "actions": np.stack(self._actions).astype(np.int32),
            "behavior_logits": np.stack(self._logits),
            "core_state": self._unroll_start_state,
        }
        self._frames = [self._frames[-1]]
        self._actions, self._logits = [], []
        self._unroll_start_state = self.core_state
        return unroll

    def record_action(self, action, behavior_logits, new_core_state=None):
        self._actions.append(np.asarray(action))
        self._logits.append(np.asarray(behavior_logits, np.float32))
        if new_core_state is not None:
            self.core_state = new_core_state


def _aligned_zeros(shape, dtype):
    """Zeros at an address the CPU backend takes without a copy: the case
    in which ``jnp.asarray`` of a pool's view is that view."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    raw = np.zeros(nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    return raw[start:start + nbytes].view(dtype).reshape(shape)


class _FakePool:
    """EnvPool-shaped outputs from a seed: per actor batch one set of
    buffers that every step overwrites, handed out as they are, the way the
    pool hands out views over its shared memory. A consumer that keeps a
    view instead of copying sees the next step's frame."""

    def __init__(self, seed, num_batches, batch_size, dict_obs):
        self.rng = np.random.default_rng(seed)
        self.b = batch_size
        obs = (
            {"glyphs": np.zeros((batch_size, 4, 6), np.int16),
             "blstats": np.zeros((batch_size, 7), np.float32)}
            if dict_obs else
            {"obs": np.zeros((batch_size, 6, 6, 4), np.uint8)}
        )
        self.bufs = [
            dict(
                {k: _aligned_zeros(v.shape, v.dtype) for k, v in obs.items()},
                done=np.zeros(batch_size, bool),
                reward=np.zeros(batch_size, np.float64),
                episode_return=np.zeros(batch_size, np.float32),
                episode_step=np.zeros(batch_size, np.int32),
            )
            for _ in range(num_batches)
        ]

    def step(self, i):
        buf, rng = self.bufs[i], self.rng
        for k, v in buf.items():
            if k == "done":
                v[:] = rng.random(self.b) < 0.2
            elif v.dtype.kind == "f":
                v[:] = rng.standard_normal(v.shape)
            else:
                v[:] = rng.integers(0, 100, v.shape)
        return buf

    def act(self):
        """An action, its logits and a new core state for one batch."""
        rng = self.rng
        core = tuple(
            jnp.asarray(rng.standard_normal((self.b, 4)), jnp.float32)
            for _ in range(2)
        )
        return (
            rng.integers(0, NUM_ACTIONS, self.b).astype(np.int64),
            rng.standard_normal((self.b, NUM_ACTIONS)).astype(np.float32),
            core,
        )


def _initial_core(batch_size):
    return (jnp.zeros((batch_size, 4)), jnp.ones((batch_size, 4)))


def _snapshot(batch):
    """A learn batch as host arrays of its own."""
    return jax.tree_util.tree_map(np.array, batch)


def _assert_same(got, want):
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _drive(pool, states, n_turns, on_unroll, after_turn=None,
           device_frames=False):
    """The acting half of ``train()``'s turn over every actor batch. With
    ``device_frames`` the frame is staged as for an act call and handed to
    the batch's state with the env output, and once the act step is done
    (the copy in has read the pool's view) a worker writes over the view:
    a frame kept by reference to it would be lost."""
    for _ in range(n_turns):
        for i, bs in enumerate(states):
            out = pool.step(i)
            obs = obs_from_env_out(out)
            staged = (stage_frame(obs),) if device_frames else ()
            on_unroll(bs, bs.observe(out, *staged))
            bs.record_action(*pool.act())
            if device_frames:
                jax.block_until_ready(staged)
                for view in jax.tree_util.tree_leaves(obs):
                    view[...] = 101
        if after_turn is not None:
            after_turn()


def _take(slabs, slab, device_frames):
    """A completed slab's learn batch, on the host, and the slab given back:
    as ``train()`` stages it where the frames are on the device."""
    if device_frames:
        return _snapshot(slabs.stage(slab))
    batch = _snapshot(slab.batch)
    slabs.recycle(slab, ())
    return batch


@pytest.mark.parametrize("device_frames", [False, True],
                         ids=["host_frames", "device_frames"])
@pytest.mark.parametrize(
    "actor_b,num_batches,learn_b,dict_obs",
    [
        (4, 2, 8, False),   # atari_loop scaled down: 2 x 128 into 256
        (4, 2, 4, False),   # learn = actor, the shipped configs
        (4, 2, 8, True),    # dict observations: a slab is a tree of arrays
        (3, 2, 4, False),   # not a multiple: a window straddles two slabs
        (4, 3, 6, True),    # the same with three actor batches and a dict
        (9, 2, 4, False),   # an unroll wider than two learn batches
    ],
)
def test_learn_batches_equal_stack_and_cat(actor_b, num_batches, learn_b,
                                           dict_obs, device_frames):
    n_turns = 6 * T + 3
    name = f"test_equal_{device_frames}"
    before = _counters(name)
    batcher_module._assemble_obs.clear_cache()
    want, got = [], []

    ref_pool = _FakePool(7, num_batches, actor_b, dict_obs)
    batcher = Batcher(batch_size=learn_b, dim=1, dims={"core_state": 0})

    def ref_unroll(bs, unroll):
        if unroll is not None:
            batcher.cat(unroll)
            while not batcher.empty():
                want.append(batcher.get())

    _drive(
        ref_pool,
        [_StackingBatchState(T, _initial_core(actor_b))
         for _ in range(num_batches)],
        n_turns, ref_unroll,
    )

    pool = _FakePool(7, num_batches, actor_b, dict_obs)
    slabs = LearnSlabs(T, learn_b, name=name)

    def slab_unroll(bs, complete):
        if complete:
            bs.start_unroll(True)

    def take():
        # As train() does after the acting half: take what is complete,
        # hand the slab back for the next fill.
        while not slabs.empty():
            got.append(_take(slabs, slabs.get(), device_frames))

    _drive(
        pool,
        [EnvBatchState(T, _initial_core(actor_b), slabs=slabs)
         for _ in range(num_batches)],
        n_turns, slab_unroll, take, device_frames,
    )

    assert len(want) == (6 * num_batches * actor_b) // learn_b
    assert len(got) == len(want)
    for g, w in zip(got, want):
        _assert_same(g, w)
    counted = {k: v - before[k] for k, v in _counters(name).items()}
    assert counted["learn_slab_batches_total"] == len(got)
    assert counted["learn_slab_device_obs_batches_total"] == (
        len(got) if device_frames else 0
    )
    # The assembly program is traced once for a loop's shapes: once in all
    # where the learn batch is whole windows, once for each way a window is
    # cut where it is not (they come round), and never for host frames.
    assert batcher_module._assemble_obs._cache_size() == (
        math.lcm(actor_b, learn_b) // learn_b if device_frames else 0
    )
    # The bootstrap overlap within one actor batch's columns: row T of an
    # unroll is row 0 of the next in the same columns' next batch, when
    # learn = actor keeps one actor batch to one learn batch.
    if learn_b == actor_b:
        for older, newer in zip(got, got[num_batches:]):
            _assert_same(
                jax.tree_util.tree_map(lambda x: x[0], newer["obs"]),
                jax.tree_util.tree_map(lambda x: x[T], older["obs"]),
            )


def test_batch_kept_is_unchanged_by_later_fills():
    pool = _FakePool(3, 2, 4, False)
    slabs = LearnSlabs(T, 8, name="test_kept")
    kept = []

    def on_unroll(bs, complete):
        if complete:
            bs.start_unroll(True)
            if not slabs.empty():
                batch = slabs.get().batch  # never recycled: ours for good
                kept.append((batch, _snapshot(batch)))

    _drive(pool, [EnvBatchState(T, (), slabs=slabs) for _ in range(2)],
           4 * T + 1, on_unroll)
    assert len(kept) == 4
    for batch, then in kept:
        _assert_same(batch, then)


class _Transfer:
    """Stands in for the device arrays staged from a slab: the transfer is
    reading the slab's arrays until ``block_until_ready`` returns, so they
    must hold then what they held when it was dispatched."""

    def __init__(self, batch):
        self.batch = batch
        self.dispatched = _snapshot(batch)
        self.waited = 0

    def block_until_ready(self):
        _assert_same(self.batch, self.dispatched)
        self.waited += 1
        return self


def test_slab_is_not_written_before_its_transfer_is_ready():
    pool = _FakePool(5, 2, 4, False)
    slabs = LearnSlabs(T, 8, name="test_guard")
    transfers = []

    def take():
        while not slabs.empty():
            slab = slabs.get()
            transfers.append(_Transfer(slab.batch))
            slabs.recycle(slab, transfers[-1])

    def on_unroll(bs, complete):
        if complete:
            bs.start_unroll(True)

    _drive(pool, [EnvBatchState(T, (), slabs=slabs) for _ in range(2)],
           5 * T + 1, on_unroll, take)
    assert len(transfers) == 5
    # Two slabs alternate: each but the newest was waited for, once, when
    # its slab went back into use, and found the slab as it was staged.
    assert [t.waited for t in transfers] == [1, 1, 1, 1, 0]
    # ... and was written after that: the fill that followed changed it.
    first = transfers[0]
    assert not np.array_equal(first.batch["obs"], first.dispatched["obs"])
    counters = _counters("test_guard")
    assert counters["learn_slab_batches_total"] == 5
    assert counters["learn_slab_reuse_waits_total"] == 4
    assert 0 <= counters["learn_slab_reuse_wait_seconds_total"] < 5
    assert counters["learn_slab_rewinds_total"] == 0
    assert counters["learn_slab_device_obs_batches_total"] == 0


def _counters(name):
    reg = global_telemetry().registry
    return {
        key: reg.counter(key, slabs=name).value
        for key in (
            "learn_slab_batches_total",
            "learn_slab_device_obs_batches_total",
            "learn_slab_reuse_waits_total",
            "learn_slab_reuse_wait_seconds_total",
            "learn_slab_rewinds_total",
        )
    }


@pytest.mark.parametrize("device_frames", [False, True],
                         ids=["host_frames", "device_frames"])
def test_dropped_unroll_leaves_no_rows_and_is_counted(device_frames):
    """Backpressure as ``train()`` applies it: the second unroll of actor
    batch 0 is dropped. Its columns are written again, so every learn batch
    is made of kept unrolls only, in the columns their actor batch holds;
    of frames kept on the device, the dropped unroll's are let go."""
    num_batches, actor_b = 2, 4
    pool = _FakePool(11, num_batches, actor_b, False)
    ref_pool = _FakePool(11, num_batches, actor_b, False)
    name = f"test_drop_{device_frames}"
    slabs = LearnSlabs(T, 8, name=name)
    states = [EnvBatchState(T, _initial_core(actor_b), slabs=slabs)
              for _ in range(num_batches)]
    refs = [_StackingBatchState(T, _initial_core(actor_b))
            for _ in range(num_batches)]
    unrolls = [[] for _ in range(num_batches)]  # the reference's, per batch
    completed = [0] * num_batches
    dropped_unrolls = 0
    got = []

    def ref_unroll(bs, unroll):
        if unroll is not None:
            unrolls[refs.index(bs)].append(unroll)

    def on_unroll(bs, complete):
        nonlocal dropped_unrolls
        if complete:
            i = states.index(bs)
            completed[i] += 1
            keep = not (i == 0 and completed[i] == 2)
            bs.start_unroll(keep)
            if not keep:
                dropped_unrolls += 1

    def take():
        while not slabs.empty():
            got.append(_take(slabs, slabs.get(), device_frames))

    _drive(ref_pool, refs, 4 * T + 1, ref_unroll)
    _drive(pool, states, 4 * T + 1, on_unroll, take, device_frames)

    assert dropped_unrolls == 1
    counters = _counters(name)
    assert counters["learn_slab_rewinds_total"] == 1
    assert counters["learn_slab_batches_total"] == len(got) == 3
    assert counters["learn_slab_device_obs_batches_total"] == (
        3 if device_frames else 0
    )
    # Each learn batch as (actor batch, its unroll) per block of columns.
    # Batch 0's unroll 1 is in none. Dropping it left batch 0 in columns
    # 0:4 of the second slab while batch 1 moved on, so batch 1 was first
    # into the third.
    layout = [
        [(0, 0), (1, 0)],
        [(0, 2), (1, 1)],
        [(1, 2), (0, 3)],
    ]
    for batch, blocks in zip(got, layout):
        for n, (i, u) in enumerate(blocks):
            cols = slice(n * actor_b, (n + 1) * actor_b)
            want = unrolls[i][u]
            for key in ("obs", "done", "rewards", "actions",
                        "behavior_logits"):
                np.testing.assert_array_equal(batch[key][:, cols], want[key])
            _assert_same(
                tuple(c[cols] for c in batch["core_state"]),
                tuple(np.asarray(c) for c in want["core_state"]),
            )


def test_env_batch_state_contract():
    """As a2c and remote_actors use it: an unroll every T frames, shaped
    for the learner, frame T carried over as frame 0, episode returns
    harvested on ``done``, and an unroll handed out never written again."""
    actor_b = 4
    pool = _FakePool(13, 1, actor_b, True)
    ref_pool = _FakePool(13, 1, actor_b, True)
    bs = EnvBatchState(T, _initial_core(actor_b))
    ref = _StackingBatchState(T, _initial_core(actor_b))
    unrolls, want, returns, lengths = [], [], [], []

    def on_unroll(state, unroll):
        if unroll is not None:
            unrolls.append((unroll, _snapshot(unroll)))

    for n in range(3 * T + 1):
        out = pool.step(0)
        returns += [float(r) for r in out["episode_return"][out["done"]]]
        lengths += [float(s) for s in out["episode_step"][out["done"]]]
        unroll = bs.observe(out)
        assert (unroll is not None) == (n > 0 and n % T == 0)
        on_unroll(bs, unroll)
        bs.record_action(*pool.act())
        ref_unroll = ref.observe(ref_pool.step(0))
        if ref_unroll is not None:
            want.append(ref_unroll)
        ref.record_action(*ref_pool.act())

    assert len(unrolls) == 3 and returns
    assert bs.recent_returns() == returns and bs.recent_returns() == []
    assert bs.recent_lengths(clear=False) == lengths
    first = unrolls[0][0]
    assert list(first) == ["obs", "done", "rewards", "actions",
                           "behavior_logits", "core_state"]
    assert first["obs"]["glyphs"].shape == (T + 1, actor_b, 4, 6)
    assert first["obs"]["glyphs"].dtype == np.int16
    assert first["done"].shape == (T + 1, actor_b)
    assert first["rewards"].dtype == np.float32
    assert first["actions"].shape == (T, actor_b)
    assert first["actions"].dtype == np.int32
    assert first["behavior_logits"].shape == (T, actor_b, NUM_ACTIONS)
    for (unroll, then), ref_unroll in zip(unrolls, want):
        _assert_same(unroll, then)  # intact after T and 2T more frames
        _assert_same(unroll, ref_unroll)
    for (older, _), (newer, _) in zip(unrolls, unrolls[1:]):
        np.testing.assert_array_equal(
            newer["obs"]["blstats"][0], older["obs"]["blstats"][T]
        )
        np.testing.assert_array_equal(newer["done"][0], older["done"][T])


def test_action_before_unroll_start_is_refused():
    """The frame that completes a shared unroll must be answered with
    ``start_unroll`` before the next action: the slab has no row T of
    actions to take it."""
    pool = _FakePool(17, 1, 4, False)
    slabs = LearnSlabs(T, 4, name="test_refused")
    bs = EnvBatchState(T, (), slabs=slabs)
    for _ in range(T):
        assert bs.observe(pool.step(0)) is None
        bs.record_action(*pool.act())
    assert bs.observe(pool.step(0)) is True
    with pytest.raises(IndexError):
        bs.record_action(*pool.act())


def test_learn_slabs_argument_and_empty_get():
    with pytest.raises(ValueError):
        LearnSlabs(0, 4)
    with pytest.raises(ValueError):
        LearnSlabs(T, 0)
    slabs = LearnSlabs(T, 4, name="test_args")
    assert slabs.empty() and slabs.ready() == 0
    with pytest.raises(RuntimeError):
        slabs.get()


def test_stage_frame_owns_its_memory():
    """What the slabs keep for T+1 turns is not the pool's view: the CPU
    backend would take an aligned host array as it is."""
    view = {"glyphs": _aligned_zeros((4, 4, 6), np.int16),
            "blstats": _aligned_zeros((4, 7), np.float32)}
    staged = stage_frame(view)
    assert all(isinstance(x, jax.Array)
               for x in jax.tree_util.tree_leaves(staged))
    jax.block_until_ready(staged)
    for v in view.values():
        v[...] = 7
    for x in jax.tree_util.tree_leaves(staged):
        assert not np.asarray(x).any()


def test_frames_kept_and_copied_in_one_learn_batch_are_refused():
    """A learn batch's observation is assembled on the device or in the
    host slab, not half and half: the slab that completes with both says
    so, and hands out no batch with rows missing."""
    pool = _FakePool(19, 2, 4, False)
    slabs = LearnSlabs(T, 8, name="test_mixed")
    states = [EnvBatchState(T, (), slabs=slabs) for _ in range(2)]
    for t in range(T + 1):
        for i, bs in enumerate(states):
            out = pool.step(i)
            staged = stage_frame(obs_from_env_out(out)) if i == 0 else None
            complete = bs.observe(out, staged)
            if t < T:
                bs.record_action(*pool.act())
    assert complete is True
    states[0].start_unroll(True)
    with pytest.raises(ValueError, match="kept on the device or copied"):
        states[1].start_unroll(True)
    assert slabs.empty()
