#!/usr/bin/env python3
"""``run.py`` with the decoder's block diffusion or its set-valued action
broken underneath it, for ``test_blockdiff_rehearsal.py``: a whole run
through the harness has to come out ``correct: false``.

    python broken_sdar_run.py <fault> --workload ... (run.py's arguments)

Faults, each one this mechanism invites: ``own_block_clean`` (a masked
copy reads its own block's clean rows, rank ``b + 1`` on a noisy query:
the classic leak, which makes every masked token trivially predictable),
``local_causal`` (the in-block part sees its own block in one direction
only), ``wrong_copy`` (a token is scored in the copy after the one it was
revealed from, where it already stands in the input), ``clip_per_token``
(the importance ratios are clipped a token and then multiplied, not
multiplied and then clipped), ``no_qk_norm`` (the query/key norm is
dropped), ``none``.
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
    from moolib_tpu.ops import vtrace

    if fault == "own_block_clean":
        real = lm.attend

        def leaking(q, k, v, ids, *, kv_seg_bt, **kw):
            # every query one rank up: its own block's clean keys count
            # as earlier (the clean copy then reads its block twice, the
            # masked copies read what they are asked to predict)
            return real(q, k, v, ids + 1, kv_seg_bt=kv_seg_bt, **kw)

        lm.attend = leaking
    elif fault == "local_causal":
        real_core = lm.blockdiff_attention

        def core(q, k, v, ids, spec, **kw):
            real_einsum = jnp.einsum

            def einsum(subscripts, *operands, **ekw):
                out = real_einsum(subscripts, *operands, **ekw)
                if subscripts == "bhcgnqd,bhcnkd->bhcgnqk":  # the scores
                    n = out.shape[-1]
                    out = jnp.where(
                        jnp.tril(jnp.ones((n, n), bool)), out, -1e30)
                return out

            jnp.einsum = einsum
            try:
                return real_core(q, k, v, ids, spec, **kw)
            finally:
                jnp.einsum = real_einsum

        lm.blockdiff_attention = core
    elif fault == "wrong_copy":
        real = lm.DecoderLM._copies

        def copies(self, obs, done):
            rows, scored_in, ids, positions = real(self, obs, done)
            later = jnp.minimum(scored_in + 1, self.diffusion.steps - 1)
            return rows, later, ids, positions

        lm.DecoderLM._copies = copies
    elif fault == "clip_per_token":
        real = vtrace.group_sum

        def clipped(x, action_step, steps):
            # the stacked token quantities: the first is log rho
            return real(
                x.at[..., 0].set(jnp.minimum(x[..., 0], 0.0)), action_step,
                steps)

        vtrace.group_sum = clipped
    elif fault == "no_qk_norm":
        real = lm.RMSNorm.__call__

        def call(self, x):
            if self.name in ("q_norm", "k_norm"):
                real(self, x)  # the gains stay in the tree, and unread
                return x
            return real(self, x)

        lm.RMSNorm.__call__ = call
    else:
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    import run as bench_run

    fault = sys.argv[1]
    if fault != "none":
        install(fault)
    sys.exit(bench_run.main(sys.argv[2:]))
