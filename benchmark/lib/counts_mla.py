"""Operations and least bytes of one training step of a decoder with
latent attention, a dense first MLP, shared experts beside routed ones
and a multi-token-prediction module, counted from its description
(``config["model"]["kwargs"]``) and from what the step itself reports, by
``lib/counts_lm.py``'s conventions: a multiply-accumulate is 2 FLOPs, a
training step costs 3x the forward pass, nothing recomputed counts (a
block rebuilt in the backward pass: not counted), elementwise work is left
out; experts by the assignments held, attention by the visible part of
the score matrix (pairs for the model's FLOPs, whole tiles for the
kernels' roofline).

A block is one entry of ``layers`` times its ``repeat``; the prediction
module's block is one more, with its projection and a second pass through
the head.
"""

from __future__ import annotations

from . import counts_lm
from .counts import TRAIN_FLOPS_MULTIPLIER


def blocks(model: dict) -> list:
    """``(attention kind, mlp kind)`` of every block a forward pass runs:
    the stack's, each as often as it repeats, then the module's."""
    out = [
        (l["attention"], l["mlp"])
        for l in model["layers"] for _ in range(l.get("repeat", 1))
    ]
    if model.get("mtp"):
        out.append((model["mtp"]["attention"], model["mtp"]["mlp"]))
    return out


def latent_projection_flops(model: dict, latent: dict) -> int:
    """One position through both low-rank paths and the output
    projection."""
    d, H = model["hidden_size"], model["num_heads"]
    qk = latent["qk_nope_head_dim"] + latent["qk_rope_head_dim"]
    return 2 * (
        d * latent["q_lora_rank"]
        + latent["q_lora_rank"] * H * qk
        + d * (latent["kv_lora_rank"] + latent["qk_rope_head_dim"])
        + latent["kv_lora_rank"] * H * (
            latent["qk_nope_head_dim"] + latent["v_head_dim"])
        + H * latent["v_head_dim"] * d
    )


def pair_flops(model: dict, latent: dict) -> int:
    """One visible (query, key) pair, every head: q.k over the query/key
    head and p.v over the value head."""
    qk = latent["qk_nope_head_dim"] + latent["qk_rope_head_dim"]
    return 2 * model["num_heads"] * (qk + latent["v_head_dim"])


def forward_flops(model: dict, tokens: int, assignments_held: float,
                  done_column) -> dict:
    """Forward FLOPs of one step over ``tokens`` positions of one packed
    sequence, by part. ``assignments_held``: the step's own counter,
    summed over every expert layer (the module's too)."""
    d, V = model["hidden_size"], model["vocab_size"]
    f = model["moe_intermediate_size"]
    seg = counts_lm.segments(done_column)
    block = min(model["attention_block"], tokens)
    parts = dict.fromkeys(
        ("mla_projections", "attention_pairs", "attention_tiles",
         "mlp_dense", "router", "experts_shared", "experts_routed",
         "mtp_projection", "heads"), 0,
    )
    for attention, mlp in blocks(model):
        kind = model["attention_kinds"][attention]
        latent, window = kind["latent"], kind["window"]
        parts["mla_projections"] += tokens * latent_projection_flops(
            model, latent)
        per_pair = pair_flops(model, latent)
        parts["attention_pairs"] += per_pair * counts_lm.visible_pairs(
            seg, window)
        parts["attention_tiles"] += (
            per_pair * block * block
            * counts_lm.visible_tiles(seg, block, window)
        )
        if mlp == "dense":
            parts["mlp_dense"] += tokens * 3 * 2 * d * model[
                "intermediate_size"]
        else:
            parts["router"] += tokens * 2 * d * model["num_experts"]
            if model.get("shared_expert_size"):
                parts["experts_shared"] += tokens * 3 * 2 * d * model[
                    "shared_expert_size"]
    parts["experts_routed"] = assignments_held * 3 * 2 * d * f
    parts["heads"] = tokens * 2 * d * (V + 1)
    if model.get("mtp"):
        parts["mtp_projection"] = tokens * 2 * (2 * d) * d
        parts["heads"] += tokens * 2 * d * V
    return parts


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    return TRAIN_FLOPS_MULTIPLIER * sum(
        v for k, v in parts.items() if k != "attention_tiles"
    )


def attention_least(model: dict, parts: dict, tokens: int,
                    peaks: dict) -> dict:
    """The attention cores over a whole step: FLOPs of the visible tiles
    (3x forward) and least bytes (every head's q, k, v and output, read or
    written once forward and once backward with their gradients, 2 B; the
    decompressed form has as many key and value heads as query heads)."""
    least_bytes = 0
    for attention, _ in blocks(model):
        latent = model["attention_kinds"][attention]["latent"]
        qk = latent["qk_nope_head_dim"] + latent["qk_rope_head_dim"]
        least_bytes += tokens * model["num_heads"] * (
            2 * qk + 2 * latent["v_head_dim"]
        ) * 2 * 3
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * parts["attention_tiles"], least_bytes, peaks
    )
