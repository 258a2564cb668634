"""Rollout bookkeeping shared by the examples.

Capability parity with the reference's ``examples/common``
(reference: examples/common/__init__.py — StatMean/StatSum, EnvBatchState
per-batch RNN-state/reward bookkeeping + time batching at :154-207; the
cluster-wide stats accumulator now lives in the library proper,
:mod:`moolib_tpu.parallel.stats`).

``EnvBatchState`` turns a stream of per-step EnvPool outputs + actions into
time-major learn-unrolls of the layout the learner expects
(:func:`moolib_tpu.learner.impala_loss` batch contract): frames overlap by
one step so frame T of one unroll is frame 0 of the next, giving every
unroll its bootstrap frame for one more copy of it.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Dict, List, Optional

import numpy as np

from moolib_tpu.ops.batcher import LearnSlabs
from moolib_tpu.utils import StatMax, StatMean, StatSum, Stats
from moolib_tpu.utils import nest  # noqa: F401  (re-export)

__all__ = [
    "EnvBatchState",
    "InProcessBroker",
    "StatMean",
    "StatSum",
    "StatMax",
    "Stats",
    "nest",
    "obs_from_env_out",
]

_ENV_OUT_RESERVED = ("action", "reward", "done", "episode_step",
                     "episode_return")


def obs_from_env_out(env_out):
    """Extract the observation from an EnvPool step dict: a bare array when
    the env observes a single array (key 'obs'), else the dict of obs
    fields (NLE-style dict observations)."""
    obs_keys = [k for k in env_out if k not in _ENV_OUT_RESERVED]
    if obs_keys == ["obs"]:
        return env_out["obs"]
    return {k: env_out[k] for k in obs_keys}


def _broker_pump_entry(wref, stop, interval):
    """Broker-pump thread entry (the weakref thread contract,
    docs/reliability.md): holds the InProcessBroker only for one update
    tick, so an abandoned broker is still collectable instead of being
    pinned forever by its own pump thread (the PR-12 bug class)."""
    while not stop.is_set():
        b = wref()
        if b is None:
            return
        b._broker.update()
        del b
        stop.wait(interval)


class InProcessBroker:
    """Broker on a background thread, for single-process runs
    (reference: the a2c example starts its own Broker in-process,
    examples/a2c.py:268-275)."""

    def __init__(self, update_interval: float = 0.05):
        import moolib_tpu
        from moolib_tpu.rpc.broker import Broker

        self.rpc = moolib_tpu.Rpc("broker")
        self.rpc.listen("127.0.0.1:0")
        self.address = self.rpc.debug_info()["listen"][0]
        self._broker = Broker(self.rpc)
        self._closed = False
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=_broker_pump_entry,
            args=(weakref.ref(self), self._stop, update_interval),
            daemon=True,
        )
        self._thread.start()

    def close(self):
        if self._closed:  # the close() idempotence contract
            return
        self._closed = True
        self._stop.set()
        self._thread.join(timeout=5)
        self.rpc.close()


class EnvBatchState:
    """Per-EnvPool-batch rollout state: RNN core state, the unroll being
    written, episode-return tracking.

    Frames and actions are written in place, once each, into buffers shaped
    as the learner wants them (:class:`moolib_tpu.ops.batcher.LearnSlabs`);
    nothing is stacked when an unroll completes. With shared slabs, an
    observation that the caller has staged on the device for its act call
    (``observe(out, staged_obs)``) is not copied on the host at all: the
    device arrays are kept for the frame's row, frame T of one unroll is
    row 0 of the next by the same reference, and the learn batch's ``obs``
    is assembled from them on the device (``LearnSlabs.stage``). ``done``,
    ``rewards``, actions and logits are copied on the host either way.

    Protocol, once per pool step (one `i` of the double buffer)::

        out = pool.step(i, actions).result()       # frame t arrives
        unroll = state.observe(out)                # may complete an unroll
        if unroll is not None: ...                 # [T+1, B, ...] / [T, B, ...]
        a, logits, core = act(params, rng, out["obs"], out["done"], state.core_state)
        state.record_action(a, logits, core)
        actions = a

    Every unroll handed out this way has buffers of its own and is never
    written again. With ``slabs`` (one ``LearnSlabs`` shared by all actor
    batches of a loop) an unroll is a window of columns of the learn batch
    itself: ``observe`` then returns True when the columns are complete,
    and the caller answers, before it acts, with ``start_unroll(keep)``::

        obs = stage_frame(out["obs"])              # on the device, its own memory
        if state.observe(out, obs):
            state.start_unroll(keep=learner_wants_it)  # False: columns written again
        a, logits, core = act(params, rng, obs, ...)
        ...
        if not slabs.empty(): batch = slabs.stage(slabs.get())  # on the device
    """

    def __init__(self, unroll_length: int, initial_core_state: Any,
                 slabs: Optional[LearnSlabs] = None):
        self.T = unroll_length
        self.core_state = initial_core_state  # state at the newest frame
        self._unroll_start_state = initial_core_state  # state at row 0
        self._shared = slabs is not None
        self._slabs = slabs  # made at the first frame when not shared
        self._window: Optional[list] = None  # where the unroll is written
        self._t = -1  # row of the newest frame
        self._n_actions = 0
        # The newest frame as the pool handed it out: views over shared
        # memory, good until this batch is stepped again (the observation
        # as the caller staged it, where it did). Held from the frame that
        # completes an unroll to start_unroll, which writes it once more,
        # as row 0 of the next.
        self._frame: Optional[tuple] = None
        # Episode stats harvested from done transitions, drained by
        # recent_returns()/recent_lengths().
        self._completed_returns: List[float] = []
        self._completed_lengths: List[float] = []

    def observe(self, env_out: Dict[str, np.ndarray], staged_obs: Any = None):
        """Feed one EnvPool output dict (frame t). Every ``unroll_length``
        frames an unroll completes: returns it (time-major, buffers of its
        own), or True where the unroll is columns of shared slabs; else
        None.

        ``staged_obs`` is the frame's observation as device arrays that own
        their memory (:func:`moolib_tpu.ops.batcher.stage_frame`: what the
        act call is given). Shared slabs keep it in
        place of a host copy; an unroll with buffers of its own is host
        arrays, and copies the observation from ``env_out`` as ever."""
        done = np.asarray(env_out["done"])
        if done.any():
            rets = np.asarray(env_out["episode_return"])[done]
            steps = np.asarray(env_out["episode_step"])[done]
            self._completed_returns.extend(float(r) for r in rets)
            self._completed_lengths.extend(float(s) for s in steps)
            # Bound both buffers: callers that never drain one must not
            # leak memory over millions of episodes.
            if len(self._completed_returns) > 10_000:
                del self._completed_returns[:-1_000]
            if len(self._completed_lengths) > 10_000:
                del self._completed_lengths[:-1_000]
        if self._window is None:
            if self._slabs is None:
                # A slab as wide as this batch, never recycled: each
                # unroll is a fresh one, the caller's to keep.
                self._slabs = LearnSlabs(self.T, len(done), name="unroll")
            self._window = self._slabs.window(len(done))
        # The copy EnvPool's zero-copy views need (the next step into this
        # buffer overwrites them), made straight into the frame's row.
        obs = (
            staged_obs if self._shared and staged_obs is not None
            else obs_from_env_out(env_out)
        )
        frame = (obs, done, env_out["reward"])
        self._t += 1
        self._slabs.write_frame(self._window, self._t, *frame)
        if self._t < self.T:
            return None
        assert self._n_actions == self.T, (
            f"{self._n_actions} actions for {self._t + 1} frames"
        )
        self._frame = frame
        if self._shared:
            return True
        self.start_unroll()
        return self._slabs.get().batch

    def start_unroll(self, keep: bool = True) -> None:
        """After the frame that completed an unroll, and before the next
        ``record_action``: commit the unroll's columns and take the next
        window (``keep``), or drop the unroll and write the same columns
        again. Frame T becomes frame 0 of the next unroll either way
        (bootstrap overlap)."""
        if keep:
            self._slabs.commit(self._window, self._unroll_start_state)
            self._window = self._slabs.window(len(self._frame[1]))
        else:
            self._slabs.rewind(self._window)
        self._unroll_start_state = self.core_state
        self._t = 0
        self._n_actions = 0
        self._slabs.write_frame(self._window, 0, *self._frame)
        self._frame = None

    def record_action(self, action, behavior_logits, new_core_state=None):
        """Record the action taken at the newest frame (and the core state
        that acting produced, which belongs to the *next* frame)."""
        self._slabs.write_action(
            self._window, self._t, action, behavior_logits
        )
        self._n_actions += 1
        if new_core_state is not None:
            self.core_state = new_core_state

    def recent_returns(self, clear: bool = True) -> List[float]:
        out = self._completed_returns
        if clear:
            self._completed_returns = []
        return out

    def recent_lengths(self, clear: bool = True) -> List[float]:
        out = self._completed_lengths
        if clear:
            self._completed_lengths = []
        return out
