#!/usr/bin/env python3
"""``run.py`` with the decoder's delta rule broken underneath it, for
``test_kda_rehearsal.py``: a whole run through the harness has to come out
``correct: false``.

    python broken_kda_run.py <fault> --workload ... (run.py's arguments)

Faults, each one this mechanism invites: ``no_reset`` (the rule's state
runs on across an episode boundary), ``state_ignored`` (the state handed
in with the batch is dropped: every sequence starts from zeros),
``beta_not_doubled`` (``beta`` in (0, 1): no negative eigenvalue),
``step_keeps_state`` (the train step returns its state unchanged),
``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    import jax.numpy as jnp

    from moolib_tpu.ops import delta_rule

    real = delta_rule.gated_delta_rule
    if fault == "no_reset":
        delta_rule.gated_delta_rule = (
            lambda q, k, v, g, beta, seg, state: real(
                q, k, v, g, beta, jnp.zeros_like(seg), state))
    elif fault == "state_ignored":
        delta_rule.gated_delta_rule = (
            lambda q, k, v, g, beta, seg, state: real(
                q, k, v, g, beta, seg, jnp.zeros_like(state)))
    elif fault == "beta_not_doubled":
        delta_rule.gated_delta_rule = (
            lambda q, k, v, g, beta, seg, state: real(
                q, k, v, g, 0.5 * beta, seg, state))
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
