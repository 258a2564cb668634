"""Launcher + plotter + tsv-record tests (reference: the launcher/plotter
scripts of examples/, exercised at function level), plus moolint CLI
tooling contracts (output formats, self-runtime budget)."""

import os
import subprocess
import sys
import time
from pathlib import Path

from moolib_tpu.examples.common.record import TsvLogger, write_metadata
from moolib_tpu.examples.launch import write_sbatch
from moolib_tpu.examples.plot import read_tsv, render

REPO_ROOT = Path(__file__).resolve().parent.parent
MOOLINT = REPO_ROOT / "tools" / "moolint.py"


def test_tsv_logger_roundtrip(tmp_path):
    path = str(tmp_path / "logs.tsv")
    log = TsvLogger(path)
    log.log({"a": 1.5, "b": "x"})
    log.log({"a": 2.5, "b": "y", "late_key": 9})  # late keys dropped
    log.log({"a": 3.5})  # missing keys -> empty
    rows = read_tsv(path)
    assert [r["a"] for r in rows] == [1.5, 2.5, 3.5]
    assert rows[0]["b"] == "x" and rows[2]["b"] == ""
    assert "late_key" not in rows[0]
    # resume adopts the existing header
    log2 = TsvLogger(path)
    log2.log({"a": 4.5, "b": "z"})
    assert read_tsv(path)[-1]["a"] == 4.5


def test_write_metadata(tmp_path):
    p = str(tmp_path / "metadata.json")
    write_metadata(p, config={"x": 1})
    import json

    meta = json.load(open(p))
    assert meta["config"] == {"x": 1} and "argv" in meta


def test_render_plot():
    pts = [(float(i), float(i * i)) for i in range(50)]
    out = render(pts, width=40, height=10, x_label="t", y_label="v")
    lines = out.splitlines()
    assert len(lines) == 12
    assert "v vs t" in lines[-1] and "50 points" in lines[-1]
    # degenerate inputs don't crash
    assert "no finite data" in render([])
    assert render([(1.0, 2.0)])


def test_write_sbatch(tmp_path):
    path = write_sbatch(
        str(tmp_path / "l.sbatch"), peers=4, broker="tcp://h:4431",
        savedir="/shared/run", overrides=["env=synthetic"],
    )
    s = open(path).read()
    assert "--array=0-3" in s
    assert "broker=tcp://h:4431" in s
    assert "peer$SLURM_ARRAY_TASK_ID" in s
    assert os.access(path, os.X_OK)


def test_broker_cli_prints_address():
    """The launcher parses the broker's stdout line (reference strategy:
    test/unit/test_broker.py exercises the CLI loop)."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "moolib_tpu.broker", "127.0.0.1:0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        line = proc.stdout.readline()
        assert "listening on" in line
        addr = line.rsplit(" ", 1)[-1].strip()
        assert addr.startswith("tcp://127.0.0.1:")
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_profile_trace_capture(tmp_path):
    """profile_trace writes an XLA trace; StepWindowProfiler opens/closes
    around the configured window without leaking an active trace."""
    import jax.numpy as jnp

    from moolib_tpu.utils.profiling import StepWindowProfiler, profile_trace

    d = str(tmp_path / "trace")
    with profile_trace(d):
        float(jnp.ones((8, 8)).sum())
    assert any(os.scandir(d)), "no trace files captured"

    p = StepWindowProfiler(str(tmp_path / "w"), start=2, stop=4)
    for i in range(6):
        p.step(i)
        float(jnp.ones((4, 4)).sum())
    p.close()
    assert any(os.scandir(str(tmp_path / "w")))

    # Disabled profiler is a no-op.
    p2 = StepWindowProfiler(None)
    p2.step(0)
    p2.close()


def test_moolint_gha_format_annotations(tmp_path):
    """--format=gha emits GitHub ::error workflow-command lines for NEW
    findings (the ci_check.sh GITHUB_ACTIONS path)."""
    bad = tmp_path / "scratch.py"
    bad.write_text(
        "import asyncio\nimport time\n\n"
        "async def handler():\n    time.sleep(1)\n"
    )
    proc = subprocess.run(
        [sys.executable, str(MOOLINT), "--format=gha", str(bad)],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    lines = [l for l in proc.stdout.splitlines() if l.startswith("::error ")]
    assert len(lines) == 1
    assert "line=5," in lines[0]
    assert "async-blocking-call" in lines[0]
    # --json stays an alias for --format=json; mixing contradictory
    # formats is rejected rather than silently picking one.
    proc = subprocess.run(
        [sys.executable, str(MOOLINT), "--json", "--format=gha", str(bad)],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
    )
    assert proc.returncode == 2
    assert "conflicts" in proc.stderr


def test_moolint_whole_repo_parses_each_file_once(monkeypatch):
    """The full ci_check.sh lint surface (package tree + tools/ + tests/,
    all rule families) must stay cheap: moolint is a tier-1 gate and a
    slow linter stops being run. What makes it slow is a rule that goes
    back to the source (a re-parse per rule, per finding or per cross-
    module lookup), so the pin is a count the run can state exactly:
    one ``ast.parse`` per linted file per ``lint_paths`` call, whichever
    rules run. The wall-clock cap lives on ci_check.sh's moolint stage
    (a duration asserted here broke under every loaded test run)."""
    import ast

    from moolib_tpu.analysis import lint_paths
    from moolib_tpu.analysis.engine import iter_py_files

    parsed = []
    real_parse = ast.parse

    def counting_parse(source, filename="<unknown>", *a, **k):
        parsed.append(filename)
        return real_parse(source, filename, *a, **k)

    monkeypatch.setattr(ast, "parse", counting_parse)
    for trees in ([REPO_ROOT / "moolib_tpu"],
                  [REPO_ROOT / "tools", REPO_ROOT / "tests"]):
        del parsed[:]
        lint_paths(trees, root=REPO_ROOT)
        files = sorted(
            p.resolve().relative_to(REPO_ROOT.resolve()).as_posix()
            for p in iter_py_files(trees)
        )
        assert len(files) > 50, files
        assert sorted(parsed) == files


def test_telemetry_dump_crawls_cohort_from_one_address(tmp_path):
    """Dialing ONE cohort member reaches the whole connected cohort: the
    __telemetry reply advertises dialable neighbours and the dump tool
    crawls them (the scraper's connection table never grows on its own —
    gossip is on demand). Connect-only peers (no listen address) are not
    advertised."""
    import json

    from moolib_tpu.rpc import Rpc
    from moolib_tpu.telemetry import Telemetry, parse_prometheus

    a, b = Rpc("crawl-a"), Rpc("crawl-b")
    lurker = Rpc("crawl-lurker", telemetry=Telemetry("l", enabled=False))
    try:
        b.define("work", lambda x: x)
        b.listen("127.0.0.1:0")
        a.listen("127.0.0.1:0")
        addr = b.debug_info()["listen"][0]
        a.connect(addr)
        lurker.connect(addr)  # connect-only: must NOT be crawled
        for i in range(5):
            assert a.sync("crawl-b", "work", i) == i
        out = tmp_path / "dump"
        proc = subprocess.run(
            [sys.executable, str(REPO_ROOT / "tools" / "telemetry_dump.py"),
             "--connect", addr, "--prometheus", "--out", str(out)],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        metrics = json.loads((out / "metrics.json").read_text())
        assert set(metrics) == {"crawl-a", "crawl-b"}, sorted(metrics)
        assert metrics["crawl-b"][
            'rpc_server_calls_total{endpoint="work"}']["value"] == 5
        for peer in ("crawl-a", "crawl-b"):
            parse_prometheus((out / f"{peer}.prom").read_text())
    finally:
        lurker.close()
        a.close()
        b.close()


def test_moolint_diff_mode_changed_untracked_and_empty():
    """--diff REF lints only files changed vs the ref: an untracked
    seeded file is picked up; paths with no changed lintable files exit
    0 with a note; a bad ref exits 2."""
    scratch = REPO_ROOT / "tests" / "_diff_scratch_tmp.py"
    scratch.write_text(
        "import asyncio\nimport time\n\n"
        "async def handler():\n    time.sleep(1)\n"
    )
    try:
        proc = subprocess.run(
            [sys.executable, str(MOOLINT), "--diff", "HEAD",
             "--no-baseline", str(scratch)],
            capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
        )
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "async-blocking-call" in proc.stdout
    finally:
        scratch.unlink()

    # Empty change set under the requested paths: clean exit, clear note.
    # (An empty in-repo dir: nothing under it can ever be changed.)
    import tempfile

    empty = tempfile.mkdtemp(dir=str(REPO_ROOT / "tests"))
    try:
        proc = subprocess.run(
            [sys.executable, str(MOOLINT), "--diff", "HEAD", empty],
            capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
        )
    finally:
        os.rmdir(empty)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no changed lintable files" in proc.stdout

    proc = subprocess.run(
        [sys.executable, str(MOOLINT), "--diff", "no-such-ref-xyz"],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
    )
    assert proc.returncode == 2
    assert "no-such-ref-xyz" in proc.stderr


def test_moolint_diff_rejects_baseline_update():
    """A diff-scoped lint sees a slice of the tree; letting it rewrite
    the whole baseline ledger would silently drop every other entry."""
    proc = subprocess.run(
        [sys.executable, str(MOOLINT), "--diff", "HEAD",
         "--baseline-update"],
        capture_output=True, text=True, cwd=str(REPO_ROOT), timeout=120,
    )
    assert proc.returncode == 2
    assert "conflicts" in proc.stderr
