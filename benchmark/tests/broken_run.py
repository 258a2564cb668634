"""``run.py`` with the timed path broken underneath it, for
test_correct.py: the harness itself is untouched, the program's factory is
replaced before the driver resolves it.

    broken_run.py <fault> --workload ... (run.py's own arguments)

Faults: ``step_keeps_state`` (the train step returns its state unchanged),
``apply_keeps_state`` (the loop's apply step does), ``half_batch`` (the
train step leaves out half of the batch's columns), ``lower_precision``
(the model is built in bfloat16 where the configuration states float32).
"""

import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def install(fault: str) -> None:
    import jax

    from moolib_tpu import learner

    if fault == "step_keeps_state":
        real = learner.make_impala_train_step

        def make(*args, **kwargs):
            kwargs["donate"] = False
            step = real(*args, **kwargs)
            return lambda state, batch: (state, step(state, batch)[1])

        learner.make_impala_train_step = make
    elif fault == "apply_keeps_state":
        learner.make_apply_step = (
            lambda optimizer, donate=True, stepscope=None:
            lambda state, grads: state
        )
    elif fault == "half_batch":
        real = learner.make_impala_train_step

        def make(*args, **kwargs):
            step = real(*args, **kwargs)

            def half(state, batch):
                B = batch["done"].shape[1]
                cut = {
                    k: jax.tree_util.tree_map(lambda x: x[:, : B // 2], v)
                    for k, v in batch.items() if k != "core_state"
                }
                cut["core_state"] = tuple(
                    x[: B // 2] for x in batch["core_state"]
                )
                return step(state, cut)

            return half

        learner.make_impala_train_step = make
    elif fault == "lower_precision":
        from benchmark.lib import program

        real = program.build_model

        def build(config):
            config = dict(config, model=dict(config["model"]))
            config["model"]["kwargs"] = dict(
                config["model"]["kwargs"], compute_dtype="bfloat16"
            )
            return real(config)

        program.build_model = build
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
