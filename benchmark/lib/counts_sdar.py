"""Operations and least bytes of one training step of a decoder under
block diffusion (``model.kwargs`` with ``diffusion``) with sparse experts,
counted from its description, the batch's episode boundaries and reveal
steps, and the assignments the step's experts held, by
``lib/counts_lm.py``'s conventions: a multiply-accumulate is 2 FLOPs, a
training step costs 3x the forward pass, nothing rebuilt counts,
elementwise work is left out. Nothing of the program is imported: these
are the numbers the step's own counters (``blockdiff_*``) are held to.

A sequence of ``L = D (N + 1)`` tokens in blocks of ``D`` runs as ``C = 1 +
S`` copies, ``C L`` rows. ``done`` lies on the step axis ``[S N + 1]``;
an episode begins at a block's first step, ``done[S b]``. Row ``(c, i)``
of block ``b`` sees the clean rows of its episode's blocks before ``b``
and its own copy's ``D`` rows of ``b``: the same count in every copy.
All ``C L`` rows are the model's own work (a copy is a state the sampler
visited, not padding): every one counts in the projections, the router
and the experts.

The flash call reads the earlier blocks alone, ``C H`` query heads on the
clean copy's ``Hkv`` key/value heads with ids ``episode << bits | block``
as group and rank: a tile of ``block x block`` is visited iff the two
blocks' groups meet and the least key rank lies under the largest query
rank (the kernels' own rule, ``ops/attention.py:_Tiles.visible``), which
for ranks that grow along the sequence is every tile on or below the
diagonal whose episodes meet.
"""

from __future__ import annotations

import numpy as np

from . import counts_lm
from .counts import TRAIN_FLOPS_MULTIPLIER

CORE_SCOPE = ("moolib.lm.attn_core",)
LOCAL_SCOPE = ("moolib.lm.blockdiff_local",)
ROWS_SCOPE = ("moolib.lm.blockdiff_rows",)
COUNTERS = ("blockdiff_rows", "blockdiff_masked_inputs",
            "blockdiff_scored_tokens", "blockdiff_steps", "blockdiff_pairs")


def spec_of(model: dict) -> dict:
    spec = model.get("diffusion")
    if not spec:
        raise ValueError("counts_sdar counts a model under block diffusion")
    return spec


def layers(model: dict) -> int:
    return sum(l.get("repeat", 1) for l in model["layers"])


def tokens_of(model: dict, done_column) -> int:
    """``L``, from the step axis: ``S N + 1`` frames."""
    spec = spec_of(model)
    steps = len(done_column) - 1
    if steps % spec["steps"]:
        raise ValueError(f"{steps} steps are no whole number of blocks")
    return spec["block"] * (steps // spec["steps"] + 1)


def block_episodes(model: dict, done_column) -> np.ndarray:
    """The episode of every block, ``[N + 1]``: the running count of
    ``done`` at the blocks' first steps."""
    return np.cumsum(
        np.asarray(done_column)[::spec_of(model)["steps"]].astype(np.int64)
    )


def pairs(model: dict, done_column) -> int:
    """Visible (query row, key row) pairs of one query head of one layer,
    all copies: counted block by block from the definition."""
    spec = spec_of(model)
    D, C = spec["block"], 1 + spec["steps"]
    episode = block_episodes(model, done_column)
    total, first = 0, 0
    for b in range(len(episode)):
        if b and episode[b] != episode[b - 1]:
            first = b
        total += D * (D * (b - first) + D)
    return C * total


def visible_tiles(model: dict, done_column) -> int:
    """Tiles one query head of the flash call visits in one layer."""
    spec = spec_of(model)
    L = tokens_of(model, done_column)
    block = min(model["attention_block"], L)
    episode = np.repeat(block_episodes(model, done_column), spec["block"])
    rank = np.arange(L) // spec["block"]
    n = L // block
    lo_e, hi_e = (episode.reshape(n, block).min(1),
                  episode.reshape(n, block).max(1))
    lo_r, hi_r = rank.reshape(n, block).min(1), rank.reshape(n, block).max(1)
    return sum(
        1 for qi in range(n) for ki in range(n)
        if lo_e[ki] <= hi_e[qi] and hi_e[ki] >= lo_e[qi]
        and lo_r[ki] < hi_r[qi]
    )


def attention_tiles(done: np.ndarray, model: dict) -> int:
    """The flash kernels' tiles of one forward pass, a query head: every
    layer and every column of ``done`` [S N + 1, B]. What a seed's
    boundaries are drawn for."""
    return layers(model) * sum(
        visible_tiles(model, done[:, b]) for b in range(done.shape[1])
    )


def counts(model: dict, done: np.ndarray, reveal: np.ndarray) -> dict:
    """What the step's ``blockdiff_*`` counters must read, from ``done``
    [S N + 1, B] and ``reveal_step`` [L, B]."""
    spec = spec_of(model)
    D, S = spec["block"], spec["steps"]
    L, B = reveal.shape
    acted = reveal[:L - D]
    masked = steps = 0
    for b in range(B):
        by_block = acted[:, b].reshape(-1, D)
        for tau in range(S):
            # copy tau shows what the steps before tau revealed
            masked += int((by_block >= tau).sum()) + D
            steps += int((by_block == tau).any(axis=1).sum())
    return {
        "blockdiff_rows": (1 + S) * L * B,
        "blockdiff_masked_inputs": masked,
        "blockdiff_scored_tokens": (L - D) * B,
        "blockdiff_steps": steps,
        "blockdiff_pairs": layers(model) * sum(
            pairs(model, done[:, b]) for b in range(B)
        ),
    }


def forward_flops(model: dict, assignments_held: float, done_column) -> dict:
    """Forward FLOPs of one step over one packed sequence, by part.
    ``assignments_held``: the step's own counter, summed over layers."""
    spec = spec_of(model)
    d, D = model["hidden_size"], model["head_dim"]
    H, Hkv = model["num_heads"], model["num_kv_heads"]
    C = 1 + spec["steps"]
    L = tokens_of(model, done_column)
    rows, n = C * L, layers(model)
    block = min(model["attention_block"], L)
    per_pair = 2 * 2 * D * H  # q.k and p.v, every query head
    return {
        "projections": n * rows * 2 * d * D * (2 * H + 2 * Hkv),
        "router": n * rows * 2 * d * model["num_experts"],
        "experts": assignments_held * 3 * 2 * d
        * model["moe_intermediate_size"],
        "attention_pairs": n * pairs(model, done_column) * per_pair,
        # the flash call's share: the C copies' heads over the visited tiles
        "attention_tiles": n * C * visible_tiles(model, done_column)
        * block * block * per_pair,
        "head": (L - spec["block"]) * 2 * d * model["vocab_size"]
        + L * 2 * d,
    }


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    return TRAIN_FLOPS_MULTIPLIER * sum(
        v for k, v in parts.items() if k != "attention_tiles"
    )


def core_least(model: dict, parts: dict, done_column, peaks: dict) -> dict:
    """The flash calls of every layer over a whole step: FLOPs of the
    visited tiles (3x forward) and least bytes (the copies' queries and
    the call's output, the clean copy's keys and values, once forward and
    once backward with their gradients, 2 B)."""
    spec = spec_of(model)
    C = 1 + spec["steps"]
    heads = 2 * C * model["num_heads"] + 2 * model["num_kv_heads"]
    least_bytes = (
        layers(model) * tokens_of(model, done_column) * model["head_dim"]
        * heads * 2 * 3
    )
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * parts["attention_tiles"], least_bytes, peaks
    )
