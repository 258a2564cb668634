"""Drive the ``vtrace_loop`` rehearsal cell on N seeds in one process (the
programs then compile once). Started by test_rehearsal.py under taskset."""

import os
import sys
import types

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def main(n: int) -> int:
    import run as bench_run

    from benchmark.drivers import vtrace_loop
    from benchmark.lib import compare, harness

    manifest = os.path.join(TESTS, "rehearsal", "BENCHMARK.json")
    root = os.path.join(os.path.dirname(manifest), "benchmark")
    cell = bench_run.load_json(
        os.path.join(root, "workloads", "tiny_atari_loop.json"))
    config = bench_run.load_json(
        os.path.join(root, "configs", "tiny_atari.json"))
    import jax

    compiles = harness.CompileLog()
    bad = 0
    for i in range(n):
        seed = 2_147_483_000 + 104_729 * i  # past 2**31 from the third on
        args = types.SimpleNamespace(seed=seed, seconds=1.0, trace=0)
        ctx = bench_run.Context(
            args, cell, config, jax.devices()[:1], compare.Verdict(),
            compiles, os.path.join(TESTS, ".trace"),
        )
        out = vtrace_loop.run(ctx)
        print(f"[seed] {seed} correct={ctx.verdict.correct} "
              f"attempted={out['attempted']} failed={out['failed']}",
              flush=True)
        bad += not ctx.verdict.correct
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1])))
