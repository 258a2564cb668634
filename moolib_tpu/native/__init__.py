"""Native runtime loader: builds and loads the C++ extension on demand.

The reference ships its native layer as a pybind11 module compiled at
install time (reference: CMakeLists.txt + src/moolib.cc). Here the extension
is a single C++ translation unit compiled with the system toolchain on
first use and cached next to the source under a name that carries a hash of
that source — a binary left over from another version of ``_native.cpp``
(or copied in with scrambled mtimes) is never loaded. Everything it
accelerates has a pure-Python fallback, so the framework works (slower)
without a compiler; falling back is logged as a warning, never silently.

Set ``MOOLIB_TPU_NO_NATIVE=1`` to force the pure-Python paths.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import glob
import hashlib
import importlib.util
import os
import subprocess
import sys
import sysconfig
import threading
from typing import Optional

from ..utils import get_logger

log = get_logger("native")

__all__ = ["get_native", "build_native"]

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "_native.cpp")

_lock = threading.Lock()
_cached = False
_module = None


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    tag = sysconfig.get_config_var("SOABI") or "unknown"
    return os.path.join(_DIR, f"_native.{digest}.{tag}.so")


def build_native(force: bool = False) -> Optional[str]:
    """Compile the extension from ``_native.cpp`` as it is on disk unless a
    build of exactly that source is cached; returns the .so path or None."""
    out = _so_path()
    if not force and os.path.exists(out):
        return out
    cxx = os.environ.get("CXX", "g++")
    include = sysconfig.get_paths()["include"]
    # Compile to a process-unique temp path and os.replace() into place:
    # concurrent first-use across processes (multi-peer launch, EnvPool
    # workers) must never dlopen a half-written .so.
    tmp = f"{out}.tmp.{os.getpid()}"
    cmd = [
        cxx, "-O2", "-std=c++17", "-shared", "-fPIC",
        f"-I{include}", _SRC, "-o", tmp, "-pthread",
    ]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning(
            "native build unavailable (%s); using pure-Python paths", e
        )
        return None
    if proc.returncode != 0:
        log.warning(
            "native build failed; using pure-Python paths:\n%s",
            proc.stderr[-2000:],
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    os.replace(tmp, out)
    for stale in glob.glob(os.path.join(_DIR, "_native.*.so")):
        if stale != out:
            try:
                os.unlink(stale)  # builds of other source versions
            except OSError:
                pass
    return out


def get_native():
    """The loaded extension module, or None (pure-Python fallback)."""
    global _cached, _module
    if _cached:
        return _module
    with _lock:
        if _cached:
            return _module
        if os.environ.get("MOOLIB_TPU_NO_NATIVE"):
            _cached = True
            return None
        so = build_native()
        if so is not None:
            try:
                spec = importlib.util.spec_from_file_location(
                    "moolib_tpu.native._native", so
                )
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                sys.modules["moolib_tpu.native._native"] = mod
                _module = mod
            except (asyncio.CancelledError,
                    concurrent.futures.CancelledError):
                raise  # never swallow task cancellation
            except Exception as e:  # corrupt cache, ABI mismatch, ...
                log.warning("native load failed (%s); rebuilding once", e)
                so = build_native(force=True)
                if so is not None:
                    try:
                        spec = importlib.util.spec_from_file_location(
                            "moolib_tpu.native._native", so
                        )
                        mod = importlib.util.module_from_spec(spec)
                        spec.loader.exec_module(mod)
                        _module = mod
                    except (asyncio.CancelledError,
                            concurrent.futures.CancelledError):
                        raise
                    except Exception as e2:
                        log.warning(
                            "native load failed after rebuild (%s); using "
                            "pure-Python paths", e2,
                        )
                        _module = None
        _cached = True
        if _module is not None:
            log.info("native runtime loaded from %s", so)
        return _module
