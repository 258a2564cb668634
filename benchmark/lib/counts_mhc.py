"""Least bytes and operations of the residual mixing of one training step
of a decoder whose blocks sit on a skeleton with ``n`` streams
(``model["residual"]``), counted from the description by
``lib/counts_lm.py``'s conventions: 2 B an element of a stream, a
multiply-accumulate 2 FLOPs, a training step 3x the forward FLOPs, nothing
rebuilt counts. ``lib/counts_mla.py`` leaves the mixing out of the model's
FLOPs; this file is its own.

A sublayer (two a block) and a token, ``n`` streams of width ``C``:

forward bytes, ``(3n + 2) C`` elements: the streams read once for the
statistics, the ``phi`` product and the weighted sum ``h`` (a fused pass
holds a token's ``n C`` values while its coefficients are made), read once
more and written once for the remix, ``h`` written and ``y`` read;

backward bytes, ``(3n + 3) C`` elements: the new streams' gradient read,
the old streams' gradient written (both of its paths in one pass), the
streams read once for the coefficients' gradients, ``y`` read, ``y``'s
gradient written and ``h``'s read;

forward FLOPs: the ``phi`` product ``2 n C (n^2 + 2n)``, the remix
``2 n^2 C``, the weighted sum and the gated write-back ``2 n C`` each. The
sigmoids, the exponentials and the Sinkhorn iterations are ``O(n^2)`` a
token and left out.
"""

from __future__ import annotations

from . import counts_lm, counts_mla
from .counts import TRAIN_FLOPS_MULTIPLIER

BYTES = 2  # a stream's element, in the computing width
# the mixing's named scopes in the program, whose device seconds the two
# readers hold these counts against
SCOPES = ("moolib.lm.hc_mix", "moolib.lm.hc_pre", "moolib.lm.hc_post")


def sublayers(model: dict) -> int:
    """Mixed sublayers a forward pass runs: two a block."""
    return 2 * len(counts_mla.blocks(model))


def per_sublayer_token(model: dict) -> dict:
    n, C = model["residual"]["streams"], model["hidden_size"]
    return {
        "forward_bytes": (3 * n + 2) * C * BYTES,
        "backward_bytes": (3 * n + 3) * C * BYTES,
        "forward_flops": (
            2 * n * C * (n * n + 2 * n) + 2 * n * n * C + 2 * 2 * n * C
        ),
    }


def mixing_least(model: dict, tokens: int, peaks: dict) -> dict:
    """The mixing of one whole step over ``tokens`` positions: its FLOPs
    (3x forward), its least bytes (forward and backward), and the least
    seconds a chip of ``peaks`` could take for the larger of the two."""
    one = per_sublayer_token(model)
    count = sublayers(model) * tokens
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * count * one["forward_flops"],
        count * (one["forward_bytes"] + one["backward_bytes"]), peaks,
    )
