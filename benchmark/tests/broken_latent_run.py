#!/usr/bin/env python3
"""``run.py`` with the latent-attention decoder broken underneath it, for
``test_latent_rehearsal.py``: a whole run through the harness has to come
out ``correct: false``.

    python broken_latent_run.py <fault> --workload ... (run.py's arguments)

Faults: ``no_bias`` (the experts are chosen by the scores alone),
``no_scale`` (the gates are not multiplied by ``routed_scaling_factor``),
``softmax`` (the router scores by softmax), ``no_shared`` (the shared
expert adds nothing), ``no_mtp_term`` (the prediction module's loss does
not reach the total), ``late_key`` (the one rotary key all heads share is turned by the
angle of the position before its own), ``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    from moolib_tpu.models import lm

    real = lm.moe_dropless
    if fault == "no_bias":
        lm.moe_dropless = lambda p, x, **kw: real(
            p, x, **dict(kw, select_bias=None))
    elif fault == "no_scale":
        lm.moe_dropless = lambda p, x, **kw: real(
            p, x, **dict(kw, gate_scale=1.0))
    elif fault == "softmax":
        lm.moe_dropless = lambda p, x, **kw: real(
            p, x, **dict(kw, scoring="softmax"))
    elif fault == "no_shared":
        import jax.numpy as jnp

        real_call = lm._GatedMlp.__call__

        def silent(self, x):
            y = real_call(self, x)
            return jnp.zeros_like(y) if self.name == "shared" else y

        lm._GatedMlp.__call__ = silent
    elif fault == "no_mtp_term":
        from benchmark.lib import program

        real_loss = program.loss_config

        def weightless(config):
            import dataclasses

            return dataclasses.replace(real_loss(config), mtp_cost=0.0)

        program.loss_config = weightless
    elif fault == "late_key":
        real_rotary = lm._rotary

        def late(x, cos, sin):
            if x.shape[2] == 1:  # the one shared rotary key: a step late
                import jax.numpy as jnp

                cos, sin = (jnp.roll(t, 1, axis=0) for t in (cos, sin))
            return real_rotary(x, cos, sin)

        lm._rotary = late
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
