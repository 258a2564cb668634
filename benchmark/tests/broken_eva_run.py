#!/usr/bin/env python3
"""``run.py`` with the decoder's chunk-summary attention broken underneath
it, for ``test_eva_rehearsal.py``: a whole run through the harness has to
come out ``correct: false``.

    python broken_eva_run.py <fault> --workload ... (run.py's arguments)

Faults: ``no_summaries`` (a query reads its own window and nothing
earlier: the second key set is left out of the softmax), ``no_mu`` (the
pooled key without its learned offset), ``mu_on_value`` (the offset added
to the pooled value instead), ``mean_pooling`` (a chunk's summary is the
plain mean of its positions: ``phi`` takes no part), ``sliding_window``
(the local set is the last ``window`` positions and not the window's block),
``step_keeps_state`` (the train step returns its state unchanged),
``wrong_direction`` (an update of the right size with the wrong sign: the
optimizer's learning rate negated; it fits where the step fits, so it is
also how that fault is read at a cell's own size), ``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    import jax.numpy as jnp

    from moolib_tpu.models import lm

    real = lm.eva_summaries
    if fault == "no_summaries":
        real_merge = lm.attn_ops.merge_attention
        lm.attn_ops.merge_attention = lambda o, lse, o2, lse2: real_merge(
            o, lse, jnp.zeros_like(o2), jnp.full_like(lse2, -1e30))
    elif fault == "no_mu":
        lm.eva_summaries = lambda k, v, own, phi, mu: real(
            k, v, own, phi, jnp.zeros_like(mu))
    elif fault == "mu_on_value":
        def moved(k, v, own, phi, mu):
            kt, vt = real(k, v, own, phi, jnp.zeros_like(mu))
            return kt, vt + mu[None, :, None].astype(vt.dtype)

        lm.eva_summaries = moved
    elif fault == "mean_pooling":
        lm.eva_summaries = lambda k, v, own, phi, mu: real(
            k, v, own, jnp.zeros_like(phi), mu)
    elif fault == "sliding_window":
        real_attend = lm.attend

        def attend(q, k, v, ids, **kw):
            if kw.get("causal", True):  # the local call: by episode alone
                bits = lm.eva_ids(ids, q.shape[2], kw["window"], 2)[3]
                return real_attend(q, k, v, ids >> bits, **kw)
            return real_attend(q, k, v, ids, **kw)

        lm.attend = attend
    elif fault == "step_keeps_state":
        from moolib_tpu import learner

        real_step = learner.make_impala_train_step

        def make(*args, **kwargs):
            step = real_step(*args, **dict(kwargs, donate=False))
            return lambda state, batch: (state, step(state, batch)[1])

        learner.make_impala_train_step = make
    elif fault == "wrong_direction":
        from benchmark.lib import program

        real_optimizer = program.build_optimizer

        def negated(config):
            opt = dict(config["optimizer"])
            opt["learning_rate"] = -opt["learning_rate"]
            return real_optimizer(dict(config, optimizer=opt))

        program.build_optimizer = negated
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
