#!/usr/bin/env python3
"""``run.py`` with the decoder's compressed attention, its router or its
head broken underneath it, for ``test_cca_rehearsal.py``: a whole run
through the harness has to come out ``correct: false``.

    python broken_cca_run.py <fault> --workload ... (run.py's arguments)

Faults, each one this mechanism invites: ``tap_across_boundary`` (the
convolutions and the value shift read the episode before),
``values_not_shifted`` (both value heads are of the token itself),
``gate_renormalised`` (the top-1 gate is ``p / p``: 1, and the router
learns nothing), ``router_state_dropped`` (no layer reads the state of
the layer before), ``skip_runs_expert_0`` (the choice that is no expert is
served by expert 0), ``head_untied`` (the head's gradient does not reach
the embedding), ``none``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def install(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from moolib_tpu.models import lm

    if fault == "tap_across_boundary":
        taps = lm._episode_taps
        lm._episode_taps = lambda x, seg_tb, tail, K: taps(
            x, jnp.zeros_like(seg_tb), tail, K)
    elif fault == "values_not_shifted":
        lm.previous_row = lambda x, seg_tb: x.astype(jnp.float32)
    elif fault == "gate_renormalised":
        real = lm.moe_dropless

        def renormalised(params, x, scores, *, select_bias, **kw):
            # the layer refuses p / p at one expert a token: hand it scores
            # that are 1 at the choice (p / p there) and 0 beside it
            top = jnp.argmax(
                scores + jax.lax.stop_gradient(select_bias), axis=-1)
            p = jnp.take_along_axis(scores, top[:, None], axis=-1)
            y, aux = real(
                params, x, jax.nn.one_hot(top, scores.shape[-1]) * (p / p),
                select_bias=select_bias, **kw)
            return y, dict(aux, moe_gate_mean=jnp.ones(()))

        lm.moe_dropless = renormalised
    elif fault == "router_state_dropped":
        real = lm._SparseMlp.__call__

        def call(self, x, z=None):
            return real(self, x, None if z is None else jnp.zeros_like(z))

        lm._SparseMlp.__call__ = call
    elif fault == "skip_runs_expert_0":
        real = lm.moe_dropless

        def served(params, x, scores, *, skip_choices, **kw):
            # the last column's probability moved onto expert 0's
            folded = scores[:, :-skip_choices].at[:, 0].add(
                jnp.sum(scores[:, -skip_choices:], axis=-1))
            bias = kw.pop("select_bias")
            return real(params, x, jnp.pad(
                folded, ((0, 0), (0, skip_choices))), skip_choices=skip_choices,
                select_bias=bias.at[-skip_choices:].set(-1e9), **kw)

        lm.moe_dropless = served
    elif fault == "head_untied":
        from flax import linen as nn

        nn.Embed.attend = lambda self, query: jnp.dot(
            query, jax.lax.stop_gradient(self.embedding).T.astype(query.dtype))
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
