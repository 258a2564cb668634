"""Entry-point tooling: the chip smoke's refusal to run without a chip, and
the compile-cache placement every ``main`` shares.

What ``chip_smoke.py`` does ON a chip is checked by running it there
(README, "Running on the chip"); what can be pinned on the CPU is that it
never passes, or compiles, without one.
"""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=120,
        cwd=REPO, env={**base, "JAX_PLATFORMS": "cpu", **env},
    )


def test_chip_smoke_without_a_chip_fails_before_compiling():
    """On the CPU the smoke exits non-zero, says which platform it found,
    prints no result line and builds no XLA program (jax logs every
    compile at WARNING under JAX_LOG_COMPILES)."""
    proc = _run(["chip_smoke.py"], JAX_LOG_COMPILES="1")
    assert proc.returncode not in (0, None), (proc.stdout, proc.stderr)
    assert "platform=cpu" in proc.stdout
    assert "'cpu'" in proc.stderr and "TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())
    assert "ompiling" not in proc.stderr, proc.stderr


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The last stdout line of a passing run is parsed by whoever runs the
    smoke: ``ok`` and ``device`` {platform, kind, count}, nothing else —
    the wall/compile summary goes on the ``[summary]`` line before it."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    )
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }
    # ... and nothing is printed after it.
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        main_body = f.read().split("def main()")[1]
    last_print = main_body[main_body.rindex("print("):]
    assert last_print.startswith("print(result_line(device)"), last_print
    assert "report(" not in last_print


def test_compile_cache_follows_the_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, jax itself reads the variable
    and the helper sets no directory in code."""
    from moolib_tpu.utils.jaxenv import enable_compile_cache

    def no_update(*a, **k):
        raise AssertionError(f"jax.config.update{a} called")

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax.config, "update", no_update)
    assert enable_compile_cache() == "/some/dir"


def test_compile_cache_default_is_one_fixed_path_in_the_checkout():
    """Without the variable, two separate processes get the same
    directory inside the checkout — the path is part of jax's cache key,
    so one that moved (tempfile, pid, timestamp) would never hit."""
    code = (
        "import json, jax\n"
        "from moolib_tpu.utils.jaxenv import enable_compile_cache\n"
        "print(json.dumps([enable_compile_cache(),"
        " jax.config.jax_compilation_cache_dir]))\n"
    )
    seen = [json.loads(_run(["-c", code]).stdout) for _ in range(2)]
    want = os.path.join(REPO, ".jax_cache")
    assert seen == [[want, want], [want, want]], seen

