"""Dynamic tensor batcher.

Capability parity with the reference's ``Batcher`` (reference:
src/moolib.cc:596-889 ``Batcher<Meta>``, Python surface at :1411-1488):
nested dict/list/tuple structures of arrays are accumulated with either
``stack`` (new leading batch dim; only full batches are emitted) or ``cat``
(concatenate along an existing dim; overflow past ``batch_size`` is split and
carried into the next batch). ``get`` blocks until a completed batch exists.

TPU twist: when a ``device`` is given, completed batches are assembled on the
host in one contiguous buffer per leaf and moved in a single
``jax.device_put`` per structure — one H2D transfer instead of per-item
copies, which is what keeps actor→HBM staging off the critical path.

:class:`LearnSlabs` is the in-place assembler of learn batches: where a
``Batcher`` copies what it is handed into a new batch, a slab *is* the batch,
and each env frame is copied once, from the EnvPool's view into its row and
columns. A frame that is on the device already, staged there for its act
call, is not copied on the host at all: the slab keeps the device arrays, and
the batch's observation is put together from them by one program on the
device (:meth:`LearnSlabs.stage`).
"""

from __future__ import annotations

import functools
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import global_telemetry
from ..utils import nest

__all__ = ["Batcher", "LearnSlabs", "stage_batch", "stage_frame"]


def stage_batch(batch: Any, device: Optional[Any]) -> Any:
    """One-shot H2D staging of a completed batch: every leaf normalized to
    a contiguous host array, then ONE ``jax.device_put`` for the whole
    structure (not one per leaf). ``device=None`` is a no-op. Shared by
    :class:`Batcher` and the serving replica's dynamic-batching loop —
    both want the same "assemble on host, move once" contract."""
    if device is None:
        return batch
    return jax.device_put(
        jax.tree_util.tree_map(np.asarray, batch), device
    )


def stage_frame(obs: Any) -> Any:
    """An env frame's observation (host arrays, as a rule views over the
    EnvPool's shared memory) as device arrays that own their memory: good
    for the act call, and for :meth:`LearnSlabs.write_frame` to keep after
    the pool has written the next frame over the views. A transfer to an
    accelerator owns what it wrote. The CPU backend aliases a host array
    that is aligned to its liking (``jnp.asarray`` and ``jax.device_put``
    alike, ``may_alias=False`` or not: jax 0.9.0), so there the frame is
    copied on the host first."""
    if jax.default_backend() == "cpu":
        obs = jax.tree_util.tree_map(np.array, obs)
    return jax.device_put(obs)


class _Slot:
    """Ordered placeholder in the ready queue: reserved under the lock at
    batch-completion time, filled outside the lock after host assembly and
    (optional) H2D staging, so transfers never block other producers or
    consumers on the Condition."""

    __slots__ = ("batch", "done")

    def __init__(self):
        self.batch = None
        self.done = False


class Batcher:
    def __init__(
        self,
        batch_size: int,
        device: Optional[Any] = None,
        dim: int = 0,
        dims: Optional[dict] = None,
        name: str = "batcher",
    ):
        """``dims`` maps top-level dict keys to a per-key batch axis
        overriding ``dim`` — e.g. learn-unrolls are [T, B, ...] (dim=1) but
        their ``core_state`` leaves are [B, ...] (dims={'core_state': 0}).
        ``name`` labels this batcher's telemetry series (several batchers
        sharing a name share counters)."""
        if batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        self.batch_size = batch_size
        self.device = device
        self.dim = dim
        self.dims = dict(dims) if dims else None
        self._lock = threading.Condition()
        self._pending_stack: list = []  # items awaiting a full stack batch
        self._pending_cat: list = []  # trees awaiting cat; rows counted below
        self._pending_cat_rows = 0
        self._ready: deque = deque()  # completed (host-side) batches
        self._closed = False
        self._async_waiters: list = []  # (loop, asyncio.Event) for __await__
        # Telemetry (process-global registry: batchers have no peer
        # identity): emitted batches/rows + time-to-fill per batch.
        self._tel = global_telemetry()
        reg = self._tel.registry
        self._m_batches = reg.counter("batcher_batches_total", batcher=name)
        self._m_rows = reg.counter("batcher_rows_total", batcher=name)
        self._m_fill_dur = reg.histogram("batcher_fill_seconds",
                                         batcher=name)
        self._fill_t0: Optional[float] = None  # first item of current batch

    # -- producer side ------------------------------------------------------

    def stack(self, tree: Any) -> None:
        """Add one unbatched structure; emits when batch_size items gathered."""
        with self._lock:
            self._check_open()
            if self._tel.on and not self._pending_stack:
                self._fill_t0 = time.monotonic()
            self._pending_stack.append(tree)
            if len(self._pending_stack) < self.batch_size:
                return
            items, self._pending_stack = (
                self._pending_stack[: self.batch_size],
                self._pending_stack[self.batch_size :],
            )
            slot = _Slot()
            self._ready.append(slot)
            self._record_emit_locked(1, self.batch_size)
        # Assemble + stage outside the lock.
        batch = self._stage(self._stack_trees(items))
        self._fill(slot, batch)

    def cat(self, tree: Any) -> None:
        """Add an already-batched structure; splits/carries past batch_size."""
        with self._lock:
            self._check_open()
            treedef = jax.tree_util.tree_structure(tree)
            rows = None
            for key, sub in self._keyed(tree):
                ax = self._axis_for(key)
                for leaf in jax.tree_util.tree_leaves(sub):
                    r = leaf.shape[ax]
                    if rows is None:
                        rows = r
                    elif r != rows:
                        raise ValueError(
                            f"inconsistent batch axis in cat(): {r} != {rows}"
                        )
            if rows is None:
                raise ValueError("cat() of an empty structure")
            if self._pending_cat:
                prev = jax.tree_util.tree_structure(self._pending_cat[0])
                if treedef != prev:
                    raise ValueError(
                        f"cat() tree structure mismatch: {treedef} != {prev}"
                    )
            if self._tel.on and not self._pending_cat:
                self._fill_t0 = time.monotonic()
            self._pending_cat.append(tree)
            self._pending_cat_rows += rows
            if self._pending_cat_rows < self.batch_size:
                return
            # One merge, then all full-batch slices in a single pass.
            merged = (
                self._cat_trees(self._pending_cat)
                if len(self._pending_cat) > 1
                else self._pending_cat[0]
            )
            total = self._pending_cat_rows
            n_full, remainder = divmod(total, self.batch_size)
            raws = [
                self._slice_tree(
                    merged, i * self.batch_size, (i + 1) * self.batch_size
                )
                for i in range(n_full)
            ]
            if remainder:
                rest = self._slice_tree(merged, total - remainder, total)
                # Copy: a view would pin the whole merged buffer in memory.
                self._pending_cat = [
                    jax.tree_util.tree_map(
                        lambda x: x if isinstance(x, jax.Array) else np.array(x),
                        rest,
                    )
                ]
            else:
                self._pending_cat = []
            self._pending_cat_rows = remainder
            slots = [_Slot() for _ in raws]
            self._ready.extend(slots)
            self._record_emit_locked(len(slots), len(slots) * self.batch_size)
        # Stage the emitted batches outside the lock, in reserved order.
        for slot, raw in zip(slots, raws):
            self._fill(slot, self._stage(raw))

    def flush(self) -> bool:
        """Emit whatever is pending as a *partial* batch (leading dim <
        ``batch_size``). Returns True when a batch was emitted, False when
        nothing was pending.

        The serving-style dynamic-batching primitive: a latency-bound
        consumer that has waited its linger budget takes the short batch
        now instead of holding requests hostage for a full one. Consumers
        that rely on static shapes (jitted handlers) should pad the
        result themselves or avoid flush()."""
        with self._lock:
            self._check_open()
            if self._pending_stack:
                items, self._pending_stack = self._pending_stack, []
                slot = _Slot()
                self._ready.append(slot)
                self._record_emit_locked(1, len(items))
                raw = None
            elif self._pending_cat:
                items = None
                raw = (
                    self._cat_trees(self._pending_cat)
                    if len(self._pending_cat) > 1
                    else self._pending_cat[0]
                )
                rows = self._pending_cat_rows
                self._pending_cat = []
                self._pending_cat_rows = 0
                slot = _Slot()
                self._ready.append(slot)
                self._record_emit_locked(1, rows)
            else:
                return False
        # Assemble + stage outside the lock (same contract as stack/cat).
        batch = raw if items is None else self._stack_trees(items)
        self._fill(slot, self._stage(batch))
        return True

    # -- consumer side ------------------------------------------------------

    def empty(self) -> bool:
        """True when no completed batch is ready (reference get/empty contract)."""
        with self._lock:
            return not (self._ready and self._ready[0].done)

    def ready(self) -> int:
        """Number of completed batches waiting to be consumed — lets callers
        apply backpressure (drop/skip) instead of queueing unboundedly."""
        with self._lock:
            return sum(1 for s in self._ready if s.done)

    def size(self) -> int:
        """Reference-surface alias for :meth:`ready` (reference:
        BatcherWrapper::size, src/moolib.cc:1915 — 'size of the batched
        queue')."""
        return self.ready()

    def __await__(self):
        """Awaitable get(): ``await batcher`` yields the next completed
        batch without blocking the event loop (reference: the Batcher is
        awaitable with asyncio, BatcherWrapper::await, src/moolib.cc:1929).

        Event-driven and cancel-safe: the awaiter registers an
        asyncio.Event that producers set via call_soon_threadsafe (the
        Queue.get_async pattern) — no idle wakeups, no added delivery
        latency, and a cancelled awaiter consumes nothing (a blocking
        ``get`` parked on an executor would survive cancellation, hang
        shutdown, and steal the next batch from the caller's fallback
        path)."""
        import asyncio

        async def anext_batch():
            loop = asyncio.get_running_loop()
            while True:
                event = asyncio.Event()
                with self._lock:
                    if self._ready and self._ready[0].done:
                        batch = self._ready.popleft().batch
                        # Wake producers parked in wait_below.
                        self._lock.notify_all()
                        return batch
                    if self._closed:
                        raise RuntimeError("Batcher is closed")
                    self._async_waiters.append((loop, event))
                await event.wait()

        return anext_batch().__await__()

    def get(self, timeout: Optional[float] = None) -> Any:
        """Block until a completed batch is available and return it.

        Raises TimeoutError on timeout and RuntimeError if closed while
        waiting with nothing buffered.
        """
        with self._lock:
            if not self._lock.wait_for(
                lambda: (self._ready and self._ready[0].done) or self._closed,
                timeout=timeout,
            ):
                raise TimeoutError("Batcher.get timed out")
            if not (self._ready and self._ready[0].done):
                raise RuntimeError("Batcher is closed")
            batch = self._ready.popleft().batch
            # Wake producers parked in wait_below (backpressure release).
            self._lock.notify_all()
            return batch

    def wait_below(self, n: int, timeout: Optional[float] = None) -> bool:
        """Block until fewer than ``n`` completed batches are queued (or the
        batcher closes). The event-driven producer-side backpressure
        primitive: wakes on actual consumption instead of polling
        ``ready()`` in a sleep loop. Returns False on timeout."""
        with self._lock:
            return self._lock.wait_for(
                lambda: self._closed
                or sum(1 for s in self._ready if s.done) < n,
                timeout=timeout,
            )

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._lock.notify_all()
            waiters, self._async_waiters = self._async_waiters, []
        for loop, event in waiters:
            try:
                loop.call_soon_threadsafe(event.set)
            except RuntimeError:
                pass

    # -- internals ----------------------------------------------------------

    def _check_open(self):
        if self._closed:
            raise RuntimeError("Batcher is closed")

    def _record_emit_locked(self, n_batches: int, n_rows: int) -> None:
        """Telemetry at batch-completion time (under self._lock)."""
        if not self._tel.on:
            return
        self._m_batches.inc(n_batches)
        self._m_rows.inc(n_rows)
        now = time.monotonic()
        if self._fill_t0 is not None:
            self._m_fill_dur.observe(now - self._fill_t0)
        # cat() carry-over rows start the next batch's fill immediately —
        # without restamping here, the "first item" stamps in add()/cat()
        # never fire again (pending is never empty) and the fill histogram
        # goes silent after the first remainder.
        self._fill_t0 = (
            now if (self._pending_stack or self._pending_cat) else None
        )

    # Per-key batch-axis plumbing (dims=): a top-level dict key may carry its
    # batch dimension on a different axis than self.dim.

    def _axis_for(self, key) -> int:
        if key is None or not self.dims:
            return self.dim
        return self.dims.get(key, self.dim)

    def _keyed(self, tree):
        if self.dims and isinstance(tree, dict):
            return list(tree.items())
        return [(None, tree)]

    def _stack_trees(self, items):
        if self.dims and isinstance(items[0], dict):
            return {
                k: nest.stack_fields(
                    [it[k] for it in items], axis=self._axis_for(k)
                )
                for k in items[0]
            }
        return nest.stack_fields(items, axis=self.dim)

    def _cat_trees(self, trees):
        if self.dims and isinstance(trees[0], dict):
            return {
                k: nest.cat_fields(
                    [t[k] for t in trees], axis=self._axis_for(k)
                )
                for k in trees[0]
            }
        return nest.cat_fields(trees, axis=self.dim)

    def _slice_tree(self, tree, start, stop):
        if self.dims and isinstance(tree, dict):
            return {
                k: nest.slice_fields(v, start, stop, self._axis_for(k))
                for k, v in tree.items()
            }
        return nest.slice_fields(tree, start, stop, self.dim)

    def _fill(self, slot: "_Slot", batch: Any) -> None:
        with self._lock:
            slot.batch = batch
            slot.done = True
            self._lock.notify_all()
            waiters, self._async_waiters = self._async_waiters, []
        for loop, event in waiters:
            try:
                loop.call_soon_threadsafe(event.set)
            except RuntimeError:
                pass  # waiter's loop already closed

    def _stage(self, batch: Any) -> Any:
        """Dispatch H2D staging at batch-completion time (producer side), so
        the async transfer overlaps accumulation of the next batch and get()
        returns an already-staged jax.Array."""
        return stage_batch(batch, self.device)


class _Slab:
    """One learn batch on the host, and what its pool keeps with it."""

    __slots__ = ("arrays", "frames", "core", "cols_done", "batch", "staged")

    def __init__(self):
        # key -> tree of host arrays [rows, B, ...], made at the key's first
        # write and kept for every later fill.
        self.arrays: dict = {}
        # first column of a piece -> (its T+1 frames kept on the device, row
        # t at index t, and the rows ``src:src+n`` of each that are the
        # piece's columns), for observations that were never copied here.
        self.frames: dict = {}
        self.core: list = []  # (first column, core state) per committed piece
        self.cols_done = 0
        self.batch: Any = None  # the learn batch, once every column is in
        self.staged: Any = None  # device arrays staged from ``arrays``


@functools.partial(jax.jit, static_argnames="cuts")
def _assemble_obs(pieces, cuts):
    """A learn batch's observation, ``[T+1, B, ...]`` per leaf, from frames
    that are on the device. ``pieces[k]`` is the ``T+1`` frames of the
    ``k``-th piece in column order, rows ``src:src+n`` of each
    (``cuts[k]``) being its columns: what stacking a piece's frames on
    axis 0 and joining the pieces on axis 1 gives, made a row at a time
    (the pieces' frames ``t`` joined, the rows stacked), which writes the
    batch once and needs no room beside it."""
    rows = [
        jax.tree_util.tree_map(
            lambda *frames: jnp.concatenate(
                [x[src:src + n] for x, (src, n) in zip(frames, cuts)]
            ),
            *frames_t,
        )
        for frames_t in zip(*pieces)
    ]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *rows)


class LearnSlabs:
    """Learn batches assembled in place, in reusable host slabs.

    A slab holds one learn batch as the learner wants it: ``obs``, ``done``
    and ``rewards`` as ``[T+1, B, ...]``, ``actions`` and
    ``behavior_logits`` as ``[T, B, ...]``. A producer (one actor batch,
    :class:`moolib_tpu.examples.common.EnvBatchState`) takes a *window* of
    columns, writes frame and action ``t`` of its unroll straight into row
    ``t`` of them, and once row ``T`` is in either commits the columns or
    writes them again (a dropped unroll). A slab whose columns are all
    committed is a learn batch: nothing is stacked or concatenated on the
    host, every frame was copied once. ``batch_size`` need not be a
    multiple of a window's width: a window may end one slab and begin the
    next, and a write is then two slice assignments per leaf.

    **A frame goes to the device once.** What :meth:`write_frame` copies
    is decided by what it is handed. An observation of host arrays is
    copied into row ``t``. An observation of device arrays (the frame as it
    was staged for its act call) is not copied: the slab keeps the arrays
    for row ``t``, and the batch's ``obs`` is made from them on the device,
    by one jitted program, when the batch is staged (:meth:`stage`): a
    piece's ``T+1`` frames stacked on axis 0, cut where a window ends one
    slab and begins the next, the pieces joined on axis 1 in column order,
    per leaf; bit for bit what the host slab would have held, and never
    transferred a second time. ``done``, ``rewards``, ``actions`` and
    ``behavior_logits`` (small) are written on the host either way. A kept
    frame has to stay whole until its batch is staged, ``T+1`` turns and
    more, while the EnvPool's view it came from is written again one turn
    later: it must own its memory, which :func:`stage_frame` sees to on
    every backend. A learn batch's observation is all of one kind: a slab
    that completes with frames kept for some of its rows and copied for
    others raises.

    ``core_state`` (``[B_actor, ...]`` device arrays, ``()`` without an
    RNN) is not written anywhere: each committed unroll's start state is
    kept, and the pieces are joined along axis 0 when the slab completes.

    The hazard of a reusable buffer: ``jax.device_put`` returns while the
    transfer still reads the host array. So :meth:`recycle` takes the
    device arrays staged from the slab, and the slab is written again only
    once they are ready. Stage with ``jax.device_put``, not
    ``jnp.asarray``: the CPU backend lets the latter alias the host array.
    A slab that is never recycled is never reused, and its batch then owns
    its memory.

    Not thread-safe: one thread writes, commits and takes.

    Telemetry (process-global, label ``slabs=``):
    ``learn_slab_batches_total`` (learn batches completed in place),
    ``learn_slab_device_obs_batches_total`` (those of them whose
    observation was assembled on the device; the difference went the
    host's way),
    ``learn_slab_reuse_waits_total`` / ``learn_slab_reuse_wait_seconds_total``
    (slabs taken back into use, and the seconds that waited for their
    transfer), ``learn_slab_rewinds_total`` (unrolls whose columns were
    written again).
    """

    def __init__(self, unroll_length: int, batch_size: int,
                 name: str = "learn_slabs"):
        if unroll_length < 1 or batch_size < 1:
            raise ValueError("unroll_length and batch_size must be >= 1")
        self.T = unroll_length
        self.batch_size = batch_size
        self._tail: Optional[_Slab] = None  # slab with columns still free
        self._col = 0  # its first free column
        self._ready: deque = deque()  # completed slabs, oldest first
        self._free: deque = deque()  # recycled slabs, oldest staging first
        self._tel = global_telemetry()
        reg = self._tel.registry
        self._m_batches = reg.counter("learn_slab_batches_total", slabs=name)
        self._m_device_obs = reg.counter(
            "learn_slab_device_obs_batches_total", slabs=name)
        self._m_reuses = reg.counter("learn_slab_reuse_waits_total",
                                     slabs=name)
        self._m_reuse_wait = reg.counter(
            "learn_slab_reuse_wait_seconds_total", slabs=name)
        self._m_rewinds = reg.counter("learn_slab_rewinds_total", slabs=name)

    # -- producer side ------------------------------------------------------

    def window(self, n_cols: int) -> list:
        """The next ``n_cols`` free columns, as pieces ``(slab, lo, hi,
        src)``: columns ``lo:hi`` of ``slab`` take rows ``src:src+hi-lo``
        of what the producer writes."""
        pieces = []
        src = 0
        while src < n_cols:
            if self._tail is None or self._col == self.batch_size:
                self._tail, self._col = self._take(), 0
            n = min(n_cols - src, self.batch_size - self._col)
            pieces.append((self._tail, self._col, self._col + n, src))
            self._col += n
            src += n
        return pieces

    def write_frame(self, window: list, t: int, obs: Any, done: Any,
                    rewards: Any) -> None:
        """Frame ``t`` of the window's unroll into row ``t``: ``done`` and
        ``rewards`` copied, ``obs`` copied too where it is host arrays and
        kept as it is where it is device arrays."""
        if all(isinstance(x, jax.Array) for x in nest.flatten(obs)):
            for slab, lo, hi, src in window:
                rows, _, _ = slab.frames.setdefault(
                    lo, ([None] * (self.T + 1), src, hi - lo)
                )
                rows[t] = obs
        else:
            self._write(window, "obs", t, obs, self.T + 1)
        self._write(window, "done", t, done, self.T + 1)
        self._write(window, "rewards", t, rewards, self.T + 1, np.float32)

    def write_action(self, window: list, t: int, actions: Any,
                     behavior_logits: Any) -> None:
        """Copy the action taken at frame ``t``, and the logits it was
        drawn from, into row ``t``."""
        self._write(window, "actions", t, actions, self.T, np.int32)
        self._write(window, "behavior_logits", t, behavior_logits, self.T,
                    np.float32)

    def commit(self, window: list, core_state: Any = ()) -> None:
        """Hand the window's columns, rows ``0..T`` written, to their
        slabs; ``core_state`` is the unroll's start state."""
        n_cols = sum(hi - lo for _, lo, hi, _ in window)
        for slab, lo, hi, src in window:
            n = hi - lo
            slab.core.append((
                lo,
                core_state if n == n_cols
                else nest.slice_fields(core_state, src, src + n, 0),
            ))
            slab.cols_done += n
            if slab.cols_done == self.batch_size:
                if slab.frames:
                    self._check_frames(slab)
                    # An earlier fill's host copy has no place in this batch.
                    slab.arrays.pop("obs", None)
                pieces = [c for _, c in sorted(slab.core, key=lambda p: p[0])]
                slab.batch = dict(
                    slab.arrays,
                    core_state=pieces[0] if len(pieces) == 1
                    else nest.cat_fields(pieces, axis=0),
                )
                self._ready.append(slab)
                if self._tel.on:
                    self._m_batches.inc()

    def rewind(self, window: list) -> None:
        """An unroll is dropped: its producer keeps the window and writes
        the columns again; the frames kept for it are let go."""
        for slab, lo, _, _ in window:
            slab.frames.pop(lo, None)
        if self._tel.on:
            self._m_rewinds.inc()

    # -- consumer side ------------------------------------------------------

    def empty(self) -> bool:
        return not self._ready

    def ready(self) -> int:
        """Completed learn batches waiting: what a producer holds against
        its backlog bound before it commits or drops."""
        return len(self._ready)

    def get(self) -> _Slab:
        """The oldest completed slab; its ``batch`` is the learn batch
        (host arrays, ``core_state`` as committed; no ``obs`` where the
        frames were kept on the device: :meth:`stage` makes it). Give the
        slab back with :meth:`stage` or :meth:`recycle`, or keep the batch
        for good."""
        if not self._ready:
            raise RuntimeError("no completed learn batch")
        return self._ready.popleft()

    def stage(self, slab: _Slab) -> dict:
        """The slab's learn batch as device arrays, and the slab given back
        for another fill. What the slab holds on the host goes in by one
        ``jax.device_put`` (not ``jnp.asarray``: the slab is written again),
        and the slab is reused once that transfer is done; ``obs`` is
        assembled on the device where the frames were kept there, one
        program for a loop's shapes."""
        frames = [slab.frames[lo] for lo in sorted(slab.frames)]
        batch = jax.device_put(slab.batch)
        self.recycle(slab, batch)
        if frames:
            batch["obs"] = _assemble_obs(
                [rows for rows, _, _ in frames],
                cuts=tuple((src, n) for _, src, n in frames),
            )
            if self._tel.on:
                self._m_device_obs.inc()
        return batch

    def recycle(self, slab: _Slab, staged: Any) -> None:
        """Give ``slab`` back for another fill. ``staged`` is what was
        made from its batch and may still be reading it: the slab is not
        written before ``jax.block_until_ready(staged)`` returns."""
        slab.batch = None
        slab.frames = {}
        slab.core = []
        slab.cols_done = 0
        slab.staged = staged
        self._free.append(slab)

    # -- internals ----------------------------------------------------------

    def _check_frames(self, slab: _Slab) -> None:
        """A completed slab that kept frames on the device kept every row
        of every piece there."""
        kept = sum(None not in rows for rows, _, _ in slab.frames.values())
        if kept != len(slab.core):
            raise ValueError(
                "a learn batch's observation is either kept on the device "
                "or copied on the host, in every row of every window: "
                f"{kept} of {len(slab.core)} windows of this one were kept "
                "whole"
            )

    def _take(self) -> _Slab:
        if not self._free:
            return _Slab()
        slab = self._free.popleft()
        t0 = time.monotonic()
        jax.block_until_ready(slab.staged)  # hotlint: sync -- reuse guard: the transfer dispatched a whole fill ago must be done reading the slab before its first row is written again
        if self._tel.on:
            self._m_reuses.inc()
            self._m_reuse_wait.inc(time.monotonic() - t0)
        slab.staged = None
        return slab

    def _write(self, window, key, t, tree, rows, dtype=None):
        for slab, lo, hi, src in window:
            dst = slab.arrays.get(key)
            if dst is None:
                dst = slab.arrays[key] = nest.map_structure(
                    lambda x: np.empty(
                        (rows, self.batch_size) + np.shape(x)[1:],
                        dtype or np.asarray(x).dtype,
                    ),
                    tree,
                )
            for d, x in zip(nest.flatten(dst), nest.flatten(tree)):
                d[t, lo:hi] = np.asarray(x)[src:src + hi - lo]
