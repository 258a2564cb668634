#!/usr/bin/env python3
"""``run.py`` with the language-model program broken underneath it, for
``test_lm_rehearsal.py``: a whole run through the harness has to come out
``correct: false``.

    python broken_lm_run.py <fault> --workload ... (run.py's arguments)

Faults: ``float8`` (the model built to compute in float8 e4m3),
``skipped_expert`` (the last expert held is left out of every layer),
``dropped_window`` (the sliding layers attend like the full ones),
``dropped_token`` (every eighth token leaves each expert layer without its
experts' output, as a layer that had no room for it would leave it),
``forced_backend`` (the model's attention calls run blockwise whatever the
configuration resolves to: the numbers stay right, the record does not),
``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    from moolib_tpu.models import lm

    if fault == "float8":
        import jax.numpy as jnp

        from benchmark.lib import program

        real_build = program.build_model

        def build(config):
            net = real_build(config)
            return net.clone(compute_dtype=jnp.dtype(jnp.float8_e4m3fn))

        program.build_model = build
    elif fault == "skipped_expert":
        real = lm.moe_dropless

        def fewer(params, x, *, top_k, held, **kw):
            first, count = held
            kept = {k: (v if k == "router" else v[:count - 1])
                    for k, v in params.items()}
            return real(kept, x, top_k=top_k, held=(first, count - 1), **kw)

        lm.moe_dropless = fewer
    elif fault == "dropped_window":
        real_attend = lm.attend

        def no_window(q, k, v, seg_bt, **kw):
            return real_attend(q, k, v, seg_bt, **dict(kw, window=None))

        lm.attend = no_window
    elif fault == "dropped_token":
        real = lm.moe_dropless

        def drops(params, x, **kw):
            y, aux = real(params, x, **kw)
            return y.at[::8].set(0), aux

        lm.moe_dropless = drops
    elif fault == "forced_backend":
        real_attend = lm.attend

        def blockwise(q, k, v, seg_bt, **kw):
            return real_attend(q, k, v, seg_bt,
                               **dict(kw, backend="blockwise"))

        lm.attend = blockwise
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
