"""Operations, least bytes and boundary counts of one training step of a
decoder whose blocks mix tokens by the gated delta rule beside softmax
blocks, counted from its description (``config["model"]["kwargs"]``) and
from the batch, by ``lib/counts_lm.py``'s conventions: a multiply-
accumulate is 2 FLOPs, a training step costs 3x the forward pass, nothing
rebuilt counts, elementwise work (norms, the convolutions' activation,
gates' sigmoids, V-trace, the optimizer) is left out; experts by the
assignments held, softmax attention by the visible pairs.

**The recurrence is counted as written**, a position and head with a
state of ``D x D``: the decay (``D^2`` multiplies), ``S^T k`` (``2 D^2``),
the rank-one update (``2 D^2``) and the read ``S^T q`` (``2 D^2``): ``7
D^2`` FLOPs. Whatever computes the scope ``moolib.lm.kda_core`` (chunks
of matrix products, a kernel) is held against that same work, so its
roofline share reads the same numerator before and after a change of
form; a chunked form does more arithmetic than this (its products over a
chunk's pairs) on faster units, and the share says what that buys.

The boundary counts are the program's ``kda_state_resets`` and
``kda_chunks_cut`` (``models/lm.py:kda_boundary_counts``), counted here
from ``done`` on the host and by another route: the driver prints both.
"""

from __future__ import annotations

import numpy as np

from . import counts_lm
from .counts import TRAIN_FLOPS_MULTIPLIER

CHUNK = 64  # positions a chunk of the recurrence: ``ops/delta_rule.py``'s
CORE_SCOPE = ("moolib.lm.kda_core",)
PROJ_SCOPE = ("moolib.lm.kda_proj",)


def blocks(model: dict) -> list:
    """The attention kind's description of every block a forward pass
    runs, each entry as often as it repeats."""
    return [
        model["attention_kinds"][l["attention"]]
        for l in model["layers"] for _ in range(l.get("repeat", 1))
    ]


def delta_blocks(model: dict) -> list:
    return [k["delta"] for k in blocks(model) if k.get("delta")]


def boundary_counts(model: dict, done_column) -> dict:
    """Every delta-rule block's, summed: positions at which the state is
    dropped, and chunks of ``CHUNK`` positions with a boundary strictly
    inside (two episodes in one chunk)."""
    done = np.asarray(done_column).astype(bool)
    T = len(done)
    chunk = CHUNK if T >= CHUNK else -(-T // 16) * 16
    inside = np.pad(done, (0, -T % chunk)).reshape(-1, chunk)[:, 1:]
    n = len(delta_blocks(model))
    return {
        "kda_state_resets": n * int(done.sum()),
        "kda_chunks_cut": n * int(inside.any(axis=1).sum()),
    }


def delta_projection_flops(model: dict, delta: dict) -> int:
    """One position through a delta-rule mixer but its recurrence: three
    projections, their convolutions, two low-rank gates, ``beta`` and the
    output projection."""
    d, width = model["hidden_size"], delta["num_heads"] * delta["head_dim"]
    rank = delta["gate_rank"]
    return 2 * (
        3 * d * width + 3 * delta["conv_size"] * width
        + 2 * (d * rank + rank * width) + d * delta["num_heads"] + width * d
    )


def core_flops(delta: dict) -> int:
    """One position of the recurrence as written, every head."""
    return 7 * delta["num_heads"] * delta["head_dim"] ** 2


def forward_flops(model: dict, tokens: int, assignments_held: float,
                  done_column) -> dict:
    """Forward FLOPs of one step over ``tokens`` positions of one packed
    sequence, by part."""
    d, D, V = model["hidden_size"], model["head_dim"], model["vocab_size"]
    H, Hkv = model["num_heads"], model["num_kv_heads"]
    seg = counts_lm.segments(done_column)
    block = min(model["attention_block"], tokens)
    parts = dict.fromkeys(
        ("softmax_projections", "attention_pairs", "attention_tiles",
         "kda_projections", "kda_core", "router", "experts_shared",
         "experts_routed", "head"), 0,
    )
    for kind in blocks(model):
        if kind.get("delta"):
            parts["kda_projections"] += tokens * delta_projection_flops(
                model, kind["delta"])
            parts["kda_core"] += tokens * core_flops(kind["delta"])
        else:
            gate = H if kind.get("output_gate") else 0
            parts["softmax_projections"] += (
                tokens * 2 * d * D * (2 * H + 2 * Hkv + gate)
            )
            per_pair = 2 * 2 * D * H
            parts["attention_pairs"] += per_pair * counts_lm.visible_pairs(
                seg, kind["window"])
            parts["attention_tiles"] += (
                per_pair * block * block
                * counts_lm.visible_tiles(seg, block, kind["window"])
            )
        parts["router"] += tokens * 2 * d * model["num_experts"]
        if model.get("shared_expert_size"):
            parts["experts_shared"] += (
                tokens * 3 * 2 * d * model["shared_expert_size"]
            )
    parts["experts_routed"] = (
        assignments_held * 3 * 2 * d * model["moe_intermediate_size"]
    )
    parts["head"] = tokens * 2 * d * (V + 1)
    return parts


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    return TRAIN_FLOPS_MULTIPLIER * sum(
        v for k, v in parts.items() if k != "attention_tiles"
    )


def core_least(model: dict, parts: dict, tokens: int, peaks: dict) -> dict:
    """The recurrence of every delta-rule block over a whole step: its
    FLOPs as written (3x forward) and its least bytes: q, k, v, the
    log-decay (a key's width each), ``beta`` and the output, read or
    written once forward and once backward with their cotangents, float32
    (what the scope is handed and hands on)."""
    least_bytes = sum(
        tokens * delta["num_heads"] * (5 * delta["head_dim"] + 1) * 4 * 3
        for delta in delta_blocks(model)
    )
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * parts["kda_core"], least_bytes, peaks
    )


def parameters(model: dict) -> int:
    """Parameters held, from the description alone."""
    d, D, V = model["hidden_size"], model["head_dim"], model["vocab_size"]
    H, Hkv = model["num_heads"], model["num_kv_heads"]
    count = (model.get("experts_held") or [0, model["num_experts"]])[1]
    f = model["moe_intermediate_size"]
    sparse = (
        d * model["num_experts"] + count * 3 * d * f
        + (model["num_experts"] if model["router"].get("selection_bias")
           else 0)
        + 3 * d * (model.get("shared_expert_size") or 0)
    )
    total = 2 * V * d + d + d + 1  # embedding, head, final norm, value unit
    for kind in blocks(model):
        delta = kind.get("delta")
        if delta:
            width, rank = delta["num_heads"] * delta["head_dim"], delta[
                "gate_rank"]
            mixer = (
                4 * d * width + 3 * delta["conv_size"] * width
                + 2 * (d * rank + rank * width) + d * delta["num_heads"]
                + delta["num_heads"] + width + delta["head_dim"]
            )
        else:
            gate = H if kind.get("output_gate") else 0
            mixer = d * D * (2 * H + 2 * Hkv + gate)
        total += mixer + sparse + 2 * d
    return total
