"""hotwatch: the dynamic mirror of the hotlint rule family.

The static rules catch the host syncs they can see lexically; this
module counts the ones that actually happen. A :class:`Hotwatch` scopes
device/host transfer accounting plus the recompile_guard compile
counters to a steady-state window — the shape the learner e2e tests and
the bench suite use: warm up outside the window, enter it, run N steps,
and any unbudgeted synchronous device->host materialization raises
:class:`HotwatchViolation` *at the offending call site* with the in-repo
stack (restrack's reporting contract: where it happened, not where it
was noticed).

Three layers, cheapest first:

- the runtime array class's ``_value`` property is patched: every
  synchronous materialization (``float()``/``.item()``/``.tolist()``/
  ``jax.device_get``/``__array__``-less paths) lands here, and
  ``_npy_value is None`` distinguishes a real transfer from a re-read
  of an already-fetched host copy;
- ``numpy.asarray``/``numpy.array`` module functions are wrapped for
  the buffer-protocol path that bypasses ``_value`` (modules that did
  ``from numpy import asarray`` keep the unwrapped function — a known
  hole the transfer-guard layer backstops);
- ``jax.transfer_guard_host_to_device("disallow")`` (when ``h2d=0``)
  and ``jax.transfer_guard_device_to_host("disallow")`` (when ``d2h=0``)
  are entered as the native backstop: on real accelerators they abort
  implicit transfers the patches cannot see. Explicit staging
  (``copy_to_host_async`` — counted as *staged*, never a violation)
  passes both guards by design.

Counting is scoped to the thread that entered the window:
``get_state``-style full-model reads on RPC/broadcast threads are their
own (already-locked) design and must not trip a step-loop window.

Compile flatness rides :mod:`moolib_tpu.analysis.recompile_guard`:
pass the jitted callables as ``jits=[...]`` and the window asserts
their combined compile-count delta stays within ``max_compiles``.

Off switch: ``MOOLIB_TPU_HOTWATCH=0`` (or ``enabled=False``) turns the
window into a no-op — nothing is patched, no guards are entered, the
hot path pays nothing.

Usage (the e2e / bench shape)::

    step = make_impala_train_step(...)          # donating jit
    run_steps(5)                                # warmup: compiles, H2D
    with Hotwatch(jits=[step]) as hw:
        run_steps(50)                           # steady state
    assert hw.d2h == 0 and hw.compile_delta == 0
"""

from __future__ import annotations

import os
import threading
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

__all__ = ["Hotwatch", "HotwatchViolation", "hotwatch_enabled"]

_PKG_ROOT = Path(__file__).resolve().parent.parent  # moolib_tpu/
_REPO_ROOT = _PKG_ROOT.parent


class HotwatchViolation(AssertionError):
    """An unbudgeted transfer (raised at the materialization site, with
    its stack) or a compile-count overrun (raised on window exit)."""


def hotwatch_enabled(default: bool = True) -> bool:
    """The environment gate: ``MOOLIB_TPU_HOTWATCH=0`` disables every
    window in the process (debug escape hatch when a guard itself is
    suspected); anything else leaves ``default``."""
    v = os.environ.get("MOOLIB_TPU_HOTWATCH", "").strip().lower()
    if v in ("0", "off", "false", "no"):
        return False
    if v in ("1", "on", "true", "yes"):
        return True
    return default


def _site_stack(limit: int = 20) -> Tuple[Optional[str], str]:
    """(innermost "path:line" or None, formatted stack trimmed to the
    interesting frames, hotwatch's own frames excluded).

    In-repo frames are preferred; when the window is driven from a
    script outside the repo (a user's own training loop), the fallback
    keeps that script's frames instead — filtering out interpreter/
    site-packages internals — so the violation still names the caller's
    line rather than an empty stack."""
    stack = traceback.extract_stack(limit=limit)
    site = outside_site = None
    kept: List[Any] = []
    outside: List[Any] = []
    for frame in stack:
        p = Path(frame.filename)
        try:
            rel = p.resolve().relative_to(_REPO_ROOT)
        except (ValueError, OSError):
            f = frame.filename
            if "site-packages" in f or f.startswith("<") \
                    or f"{os.sep}lib{os.sep}python" in f:
                continue
            outside.append(frame)
            outside_site = f"{f}:{frame.lineno}"
            continue
        if rel.parts[:2] == ("moolib_tpu", "testing") \
                and rel.name == "hotwatch.py":
            continue
        kept.append(frame)
        site = f"{rel.as_posix()}:{frame.lineno}"
    if site is not None:
        return site, "".join(traceback.format_list(kept))
    if outside_site is not None:
        return outside_site, "".join(traceback.format_list(outside))
    return None, ""


class Hotwatch:
    """Steady-state transfer/compile window.

    Parameters
    ----------
    d2h:
        Budget of *synchronous* device->host materializations allowed in
        the window (staged ``copy_to_host_async`` reads are free). The
        default 0 is the steady-state contract; exceeding the budget
        raises :class:`HotwatchViolation` at the offending site. When 0,
        the native D2H transfer guard is also entered as an
        accelerator-side backstop for paths the patches miss.
    h2d:
        ``None`` (default) leaves host->device transfers unwatched; 0
        enters ``jax.transfer_guard_host_to_device("disallow")``, so an
        un-staged per-step upload aborts with the runtime's own error.
        (H2D accounting is guard-native: budgets other than 0/None are
        not supported.)
    jits:
        Jitted callables (``jax.jit`` results or
        :class:`~moolib_tpu.analysis.recompile_guard.GuardedJit`
        wrappers) whose compile counts must stay flat across the window;
        callables with unreadable counts are skipped silently.
    max_compiles:
        Combined compile-count delta allowed across ``jits`` (default 0:
        a steady-state window never recompiles). Checked on clean exit.
    enabled:
        ``None`` consults :func:`hotwatch_enabled`; ``False`` makes the
        whole window a no-op with zero overhead (nothing patched).
    label:
        Names the window in violation messages.
    """

    def __init__(self, *, d2h: int = 0, h2d: Optional[int] = None,
                 jits: Sequence[Any] = (), max_compiles: int = 0,
                 enabled: Optional[bool] = None,
                 label: str = "hotwatch"):
        if h2d not in (None, 0):
            raise ValueError("h2d must be None (unwatched) or 0 (disallow)")
        self.d2h_budget = int(d2h)
        self.h2d = h2d
        self.jits = list(jits)
        self.max_compiles = int(max_compiles)
        self.label = label
        self.enabled = hotwatch_enabled() if enabled is None else bool(enabled)
        #: (site, stack) per counted synchronous materialization.
        self.d2h_events: List[Tuple[Optional[str], str]] = []
        #: Explicit async stagings observed (never violations).
        self.staged = 0
        self._tid: Optional[int] = None
        self._orig: Dict[str, Any] = {}
        self._guards: List[Any] = []
        self._compile_start: List[Tuple[Any, int]] = []
        self._active = False

    # -- counters -------------------------------------------------------------

    @property
    def d2h(self) -> int:
        """Synchronous materializations counted so far."""
        return len(self.d2h_events)

    @property
    def compile_delta(self) -> int:
        """Combined compile-count growth across ``jits`` since entry."""
        from moolib_tpu.analysis.recompile_guard import compile_count

        delta = 0
        for fn, start in self._compile_start:
            now = compile_count(fn)
            if now is not None:
                delta += max(0, now - start)
        return delta

    # -- the counting core ----------------------------------------------------

    def _on_transfer(self) -> None:
        """Record one synchronous materialization on the window thread;
        raise at the site once the budget is exhausted."""
        if threading.get_ident() != self._tid:
            return
        site, stack = _site_stack()
        self.d2h_events.append((site, stack))
        if self.d2h > self.d2h_budget:
            where = site or "<outside repo>"
            raise HotwatchViolation(
                f"{self.label}: unbudgeted synchronous device->host "
                f"transfer #{self.d2h} (budget {self.d2h_budget}) at "
                f"{where} — stage it with copy_to_host_async and drain "
                f"at a log boundary, or raise the window's d2h budget.\n"
                f"Materialization site:\n{stack}"
            )

    # -- patching -------------------------------------------------------------

    def _activate(self) -> None:
        import jax
        import numpy as np
        from jax._src.array import ArrayImpl as array_cls

        watch = self

        orig_value = array_cls._value
        orig_stage = array_cls.copy_to_host_async
        orig_asarray = np.asarray
        orig_array = np.array

        def patched_value(arr):
            # _npy_value is the cached host copy: None means this read
            # is a real transfer, not a re-read of fetched data.
            if getattr(arr, "_npy_value", None) is None:
                watch._on_transfer()
            return orig_value.__get__(arr)

        def patched_stage(arr, *args, **kwargs):
            if threading.get_ident() == watch._tid:
                watch.staged += 1
            return orig_stage(arr, *args, **kwargs)

        def _count_np(args):
            if args and isinstance(args[0], array_cls) \
                    and getattr(args[0], "_npy_value", None) is None:
                watch._on_transfer()

        def patched_asarray(*args, **kwargs):
            _count_np(args)
            return orig_asarray(*args, **kwargs)

        def patched_array(*args, **kwargs):
            _count_np(args)
            return orig_array(*args, **kwargs)

        self._orig = {
            "value": orig_value, "stage": orig_stage,
            "asarray": orig_asarray, "array": orig_array,
        }
        array_cls._value = property(patched_value)
        array_cls.copy_to_host_async = patched_stage
        np.asarray = patched_asarray
        np.array = patched_array

        # Native backstops. Plain "disallow" covers *implicit* transfers
        # only, so explicit staging (copy_to_host_async, device_put)
        # still passes — exactly the staged-drain discipline. The guards
        # are thread-local jax config contexts: they scope to the window
        # thread on their own.
        if self.d2h_budget == 0:
            g = jax.transfer_guard_device_to_host("disallow")
            g.__enter__()
            self._guards.append(g)
        if self.h2d == 0:
            g = jax.transfer_guard_host_to_device("disallow")
            g.__enter__()
            self._guards.append(g)

    def _deactivate(self) -> None:
        import numpy as np
        from jax._src.array import ArrayImpl

        if self._orig:
            ArrayImpl._value = self._orig["value"]
            ArrayImpl.copy_to_host_async = self._orig["stage"]
            np.asarray = self._orig["asarray"]
            np.array = self._orig["array"]
            self._orig = {}
        while self._guards:
            self._guards.pop().__exit__(None, None, None)

    # -- context protocol -----------------------------------------------------

    def __enter__(self) -> "Hotwatch":
        if not self.enabled:
            return self
        from moolib_tpu.analysis.recompile_guard import compile_count

        self._tid = threading.get_ident()
        self._compile_start = []
        for fn in self.jits:
            start = compile_count(fn)
            if start is not None:
                self._compile_start.append((fn, start))
        self._activate()
        self._active = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if not self._active:
            return False
        self._active = False
        self._deactivate()
        if exc_type is None:
            delta = self.compile_delta
            if delta > self.max_compiles:
                raise HotwatchViolation(
                    f"{self.label}: jitted step(s) compiled {delta} "
                    f"time(s) inside a window budgeted for "
                    f"{self.max_compiles} — the steady state is "
                    "retracing (changing shapes/dtypes or un-static "
                    "Python scalars)"
                )
        return False
