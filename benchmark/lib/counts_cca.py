"""Operations, least bytes and boundary counts of one training step of a
decoder of compressed convolutional attention and top-1 experts behind an
MLP router, counted from its description (``config["model"]["kwargs"]``)
and from the batch, by ``lib/counts_lm.py``'s conventions: a multiply-
accumulate is 2 FLOPs, a training step costs 3x the forward pass, nothing
rebuilt counts, elementwise work (norms, the depthwise convolution, the
query-key mean, l2norm, the rotary, the residual scales, GELU, V-trace,
the optimizer) is left out; experts by the assignments held (a token
whose choice is no expert, or an expert held elsewhere, counts nothing),
attention by the visible pairs.

**The mixing is counted as written** (``moolib.lm.cca_mix``: both
convolutions, the mean, the normalisation, the temperature, the rotary):
a token and layer, the grouped convolution's ``2 x taps x heads x D x D``
FLOPs, and as bytes ``[qt | kt]`` read once and ``qh``, ``kh`` written
once in the compute type (2 B), 3x forward for the pass and its backward.
Whatever implements the scope is held against that same work.

The boundary count is the program's ``cca_taps_cut``
(``models/lm.py:cca_taps_cut``), counted here from ``done`` on the host
and by another route: the driver prints both.
"""

from __future__ import annotations

import numpy as np

from . import counts_kda, counts_lm
from .counts import TRAIN_FLOPS_MULTIPLIER

MIX_SCOPE = ("moolib.lm.cca_mix",)
PROJ_SCOPE = ("moolib.lm.cca_proj",)
CORE_SCOPE = ("moolib.lm.attn_core",)
ROUTER_SCOPE = ("moolib.moe.router_mlp",)
SCALE_SCOPE = ("moolib.lm.residual_scale",)


def expanded(model: dict) -> dict:
    """``model`` with every repeated entry of ``layers`` written out: one
    entry a block, as ``lib/counts_lm.py`` counts them."""
    return dict(model, layers=[
        {"attention": l["attention"], "mlp": l["mlp"]}
        for l in model["layers"] for _ in range(l.get("repeat", 1))
    ])


def cca_blocks(model: dict) -> list:
    """The ``cca`` description of every such block a forward pass runs."""
    return [k["cca"] for k in counts_kda.blocks(model) if k.get("cca")]


def taps_cut(model: dict, done_column) -> int:
    """Every block's, summed: (position, tap) pairs of the two
    convolutions, the position's own tap apart, and shifted values (one
    tap back) that read zero: the row they reach for lies before the
    call's first or across a boundary."""
    done = np.asarray(done_column).astype(bool)
    T = len(done)

    def cut(back):  # positions with a boundary among the `back` up to them
        crossed = np.zeros(T, bool)
        for s in range(back):
            crossed[s:] |= done[:T - s]
        crossed[:back] = True
        return int(crossed.sum())

    return sum(
        cut(back) for cca in cca_blocks(model)
        for taps in (cca["time0"], cca["time1"], 2)
        for back in range(1, taps)
    )


def widths(model: dict):
    """(hidden, compressed query width, compressed key/value width)."""
    D = model["head_dim"]
    return model["hidden_size"], model["num_heads"] * D, (
        model["num_kv_heads"] * D
    )


def router_flops(model: dict) -> int:
    """One position through the MLP router: the down-projection, two
    hidden layers and the output."""
    r = model["router"]["hidden_size"]
    choices = model["num_experts"] + model["router"]["skip_choices"]
    return 2 * (model["hidden_size"] * r + 2 * r * r + r * choices)


def conv_flops(model: dict, cca: dict) -> int:
    """One position through the grouped convolution."""
    D = model["head_dim"]
    return 2 * cca["time1"] * (
        model["num_heads"] + model["num_kv_heads"]
    ) * D * D


def forward_flops(model: dict, tokens: int, assignments_held: float,
                  done_column) -> dict:
    """Forward FLOPs of one step over ``tokens`` positions of one packed
    sequence, by part."""
    d, q, kv = widths(model)
    seg = counts_lm.segments(done_column)
    block = min(model["attention_block"], tokens)
    per_pair = 2 * 2 * model["head_dim"] * model["num_heads"]
    parts = dict.fromkeys(
        ("cca_projections", "cca_conv", "attention_pairs",
         "attention_tiles", "router", "experts", "head"), 0,
    )
    for kind in counts_kda.blocks(model):
        parts["cca_projections"] += tokens * 2 * d * (2 * q + 2 * kv)
        parts["cca_conv"] += tokens * conv_flops(model, kind["cca"])
        parts["attention_pairs"] += per_pair * counts_lm.visible_pairs(
            seg, kind["window"])
        parts["attention_tiles"] += (
            per_pair * block * block
            * counts_lm.visible_tiles(seg, block, kind["window"])
        )
        parts["router"] += tokens * router_flops(model)
    parts["experts"] = (
        assignments_held * 3 * 2 * d * model["moe_intermediate_size"]
    )
    parts["head"] = tokens * 2 * d * (model["vocab_size"] + 1)
    return parts


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    return TRAIN_FLOPS_MULTIPLIER * sum(
        v for k, v in parts.items() if k != "attention_tiles"
    )


def mix_least(model: dict, parts: dict, tokens: int, peaks: dict) -> dict:
    """The mixing of every block over a whole step, as written (module
    docstring): the grouped convolution's FLOPs and the scope's least
    bytes, each 3x forward."""
    _, q, kv = widths(model)
    least_bytes = TRAIN_FLOPS_MULTIPLIER * len(cca_blocks(model)) * (
        tokens * 2 * (q + kv) * 2
    )
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * parts["cca_conv"], least_bytes, peaks
    )


def core_least(model: dict, parts: dict, tokens: int, peaks: dict) -> dict:
    """The attention cores of every block over a whole step, on
    ``counts_lm.attention_least``'s rule: visible tiles, 3x forward."""
    return counts_lm.attention_least(expanded(model), parts, tokens, peaks)


def parameters(model: dict) -> int:
    """Parameters held, from the description alone."""
    d, q, kv = widths(model)
    D, V = model["head_dim"], model["vocab_size"]
    r = model["router"]["hidden_size"]
    choices = model["num_experts"] + model["router"]["skip_choices"]
    count = (model.get("experts_held") or [0, model["num_experts"]])[1]
    total = V * d + d + d + 1  # the tied matrix, final norm, value unit
    for cca in cca_blocks(model):
        heads = model["num_heads"] + model["num_kv_heads"]
        attention = (
            d * q + d * kv + d * kv + (cca["time0"] + 1) * (q + kv)
            + cca["time1"] * heads * D * D + (q + kv)
            + model["num_kv_heads"] + q * d
        )
        router = (
            d * r + r + r + r + 2 * (r * r + r) + r * choices + choices
        )
        total += (
            attention + router
            + count * 3 * d * model["moe_intermediate_size"]
            + 2 * d + 8 * d
        )
    return total
