#!/usr/bin/env python3
"""``run.py`` with the decoder on several residual streams broken
underneath it, for ``test_mhc_rehearsal.py``: a whole run through the
harness has to come out ``correct: false``.

    python broken_mhc_run.py <fault> --workload ... (run.py's arguments)

Faults: ``no_iterations`` (the remix matrix is the clipped exponentials,
no Sinkhorn iteration), ``one_iteration``, ``post_gate`` (the write-back
gate is ``sigmoid`` and not ``2 sigmoid``), ``no_score_scale`` (the scores
are scaled by ``(nope + rope)^-1/2`` alone, YaRN's ``mscale^2`` left out),
``wide_value`` (the value head reads the first 8 of the key's ``nope``
dimensions instead of its own), ``ascent`` (the optimizer steps up the
gradient: every leaf's first change has the right norm and the wrong sign,
which the later steps' loss sees whatever the later changes' norms do),
``step_keeps_state``
(the train step returns its state unchanged), ``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    from moolib_tpu.models import lm

    real = lm.hyper_coefficients
    if fault in ("no_iterations", "one_iteration"):
        iters = 0 if fault == "no_iterations" else 1
        lm.hyper_coefficients = lambda *a, **kw: real(
            *a, **dict(kw, sinkhorn_iters=iters))
    elif fault == "post_gate":
        def halved(*a, **kw):
            pre, post, res, counters = real(*a, **kw)
            return pre, post / 2.0, res, counters

        lm.hyper_coefficients = halved
    elif fault == "no_score_scale":
        real_attend = lm.attend
        lm.attend = lambda *a, **kw: real_attend(*a, **dict(kw, scale=None))
    elif fault == "wide_value":
        real_attend = lm.attend

        def attend(q, k, v, *a, **kw):
            return real_attend(q, k, k[..., :v.shape[-1]], *a, **kw)

        lm.attend = attend
    elif fault == "ascent":
        from benchmark.lib import program

        real_optimizer = program.build_optimizer

        def uphill(config):
            opt = config["optimizer"]
            return real_optimizer(dict(config, optimizer=dict(
                opt, learning_rate=-opt["learning_rate"])))

        program.build_optimizer = uphill
    elif fault == "step_keeps_state":
        from moolib_tpu import learner

        real_step = learner.make_impala_train_step

        def make(*args, **kwargs):
            step = real_step(*args, **dict(kwargs, donate=False))
            return lambda state, batch: (state, step(state, batch)[1])

        learner.make_impala_train_step = make
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
