"""Operations and least bytes of one training step of a decoder with
chunk-summary attention (an attention kind with ``eva``) and dense gated
MLPs, counted from its description (``config["model"]["kwargs"]``) and the
batch's episode boundaries, by ``lib/counts_lm.py``'s conventions: a
multiply-accumulate is 2 FLOPs, a training step costs 3x the forward pass,
nothing rebuilt counts, elementwise work is left out (the chunk pooling is
multiplies and sums over 16 rows, no product: left out); attention by the
visible part of its two score matrices, pairs for the model's FLOPs and
whole tiles for the kernels' roofline.

A query ``t`` of window ``floor(t / W)`` and episode ``e`` reads

- *locally* the positions ``s <= t`` of its window and episode, and
- *through summaries* the chunks ``j`` (``c`` positions each) that lie in
  an earlier window and whose last position is of episode ``e``.

A local tile is ``block`` queries by ``block`` keys, a summary tile
``block`` queries by ``block`` chunks (fewer where the sequence has
fewer); a tile counts if it holds one visible pair. The flash kernels
skip by their blocks' least and largest ids, which can visit a summary
tile that holds none (two boundaries inside one chunk): they never skip
one that counts here.
"""

from __future__ import annotations

import numpy as np

from . import counts_lm
from .counts import TRAIN_FLOPS_MULTIPLIER

SUMMARY_SCOPES = ("moolib.lm.eva_summary", "moolib.lm.eva_merge")


def kind_of(model: dict) -> dict:
    """The one attention kind with ``eva`` that the model's layers name."""
    kinds = {
        name: kind for name, kind in model["attention_kinds"].items()
        if kind.get("eva")
    }
    if len(kinds) != 1 or any(
        l["attention"] not in kinds for l in model["layers"]
    ):
        raise ValueError("counts_eva counts a stack of one eva kind")
    return next(iter(kinds.values()))


def blocks(model: dict) -> int:
    return sum(l.get("repeat", 1) for l in model["layers"])


def chunk_ids(seg: np.ndarray, window: int, chunk: int):
    """Of every chunk: the episode of its last position, the window it
    lies in, and whether it straddles a boundary (a last chunk cut short
    by the sequence's end ends there)."""
    T = len(seg)
    first = np.arange(0, T, chunk)
    last = np.minimum(first + chunk - 1, T - 1)
    whole = first + chunk <= T
    return seg[last], first // window, (seg[first] != seg[last]) & whole


def local_pairs(seg: np.ndarray, window: int) -> int:
    """Pairs (t, s), s <= t, one episode and one window."""
    t = np.arange(len(seg))
    start = np.maximum(
        np.searchsorted(seg, seg, side="left"), t // window * window
    )
    return int((t - start + 1).sum())


def summary_pairs(seg: np.ndarray, window: int, chunk: int) -> int:
    """Pairs (t, j): chunk j of t's episode and an earlier window."""
    episode, chunk_window, _ = chunk_ids(seg, window, chunk)
    count = 0
    for w in range(1, -(-len(seg) // window)):
        earlier = episode[chunk_window < w]
        here = seg[w * window:(w + 1) * window]
        # episodes never decrease: count, for every query, the earlier
        # chunks that carry its episode
        count += int((
            np.searchsorted(earlier, here, side="right")
            - np.searchsorted(earlier, here, side="left")
        ).sum())
    return count


def local_tiles(seg: np.ndarray, block: int, window: int) -> int:
    """Tiles of ``block`` x ``block`` that hold a visible local pair."""
    T = len(seg)
    block = min(block, T)
    if window % block or T % block:
        raise ValueError(f"tiles of {block} do not divide {window}, {T}")
    n, per = T // block, window // block
    lo = seg.reshape(n, block).min(axis=1)
    hi = seg.reshape(n, block).max(axis=1)
    return sum(
        1 for qi in range(n) for ki in range(qi // per * per, qi + 1)
        if ki == qi or hi[ki] >= lo[qi]
    )


def summary_tiles(seg: np.ndarray, block: int, window: int,
                  chunk: int) -> int:
    """Tiles of ``block`` queries x ``block`` chunks that hold a visible
    (query, chunk) pair."""
    T = len(seg)
    episode, chunk_window, _ = chunk_ids(seg, window, chunk)
    block_q, block_k = min(block, T), min(block, len(episode))
    if window % block_q or T % block_q or len(episode) % block_k:
        raise ValueError(f"tiles of {block} do not divide the sequence")
    count = 0
    for qi in range(T // block_q):
        here = np.unique(seg[qi * block_q:(qi + 1) * block_q])
        w = qi * block_q // window
        for kj in range(len(episode) // block_k):
            cut = slice(kj * block_k, (kj + 1) * block_k)
            earlier = episode[cut][chunk_window[cut] < w]
            count += bool(np.intersect1d(here, earlier).size)
    return count


def attention_counts(model: dict, done_column) -> dict:
    """Of one block's attention over the batch's one column: both kinds of
    pair, both kinds of tile, the chunks cut."""
    kind = kind_of(model)
    W, c = kind["window"], kind["eva"]["chunk_size"]
    seg = counts_lm.segments(done_column)
    return {
        "local_pairs": local_pairs(seg, W),
        "summary_pairs": summary_pairs(seg, W, c),
        "local_tiles": local_tiles(seg, model["attention_block"], W),
        "summary_tiles": summary_tiles(seg, model["attention_block"], W, c),
        "chunks_cut": int(chunk_ids(seg, W, c)[2].sum()),
    }


def visible_tiles(done: np.ndarray, model: dict) -> int:
    """Local and summary tiles together, every block and every column of
    ``done`` [T+1, B]: what a seed's boundaries are drawn for."""
    total = 0
    for b in range(done.shape[1]):
        counts = attention_counts(model, done[:, b])
        total += counts["local_tiles"] + counts["summary_tiles"]
    return blocks(model) * total


def forward_flops(model: dict, tokens: int, done_column) -> dict:
    """Forward FLOPs of one step over ``tokens`` positions of one packed
    sequence, by part."""
    d, D, H = model["hidden_size"], model["head_dim"], model["num_heads"]
    Hkv = model["num_kv_heads"]
    heads = model.get("num_pred_heads", 1)
    counts = attention_counts(model, done_column)
    n = blocks(model)
    per_pair = 2 * 2 * D * H  # q.k and p.v, every query head
    block = min(model["attention_block"], tokens)
    chunks = -(-tokens // kind_of(model)["eva"]["chunk_size"])
    return {
        "projections": n * tokens * 2 * d * D * (2 * H + 2 * Hkv),
        "attention_pairs": n * per_pair * (
            counts["local_pairs"] + counts["summary_pairs"]),
        "attention_tiles": n * per_pair * block * (
            counts["local_tiles"] * block
            + counts["summary_tiles"] * min(block, chunks)),
        "mlp_dense": n * tokens * 3 * 2 * d * model["intermediate_size"],
        "heads": tokens * 2 * d * (heads * model["vocab_size"] + 1),
    }


def train_flops(parts: dict) -> float:
    """Model FLOPs of the step: 3x forward, attention by visible pairs."""
    return TRAIN_FLOPS_MULTIPLIER * sum(
        v for k, v in parts.items() if k != "attention_tiles"
    )


def attention_least(model: dict, parts: dict, tokens: int,
                    peaks: dict) -> dict:
    """Both attention calls of every block over a whole step: FLOPs of
    the visible tiles (3x forward) and least bytes (q, k, v and the merged
    output once forward and once backward with their gradients, the
    summaries' keys and values likewise, 2 B)."""
    D, H, Hkv = model["head_dim"], model["num_heads"], model["num_kv_heads"]
    chunks = -(-tokens // kind_of(model)["eva"]["chunk_size"])
    least_bytes = blocks(model) * D * (
        tokens * (2 * H + 2 * Hkv) + chunks * 2 * Hkv
    ) * 2 * 3
    return counts_lm._least(
        TRAIN_FLOPS_MULTIPLIER * parts["attention_tiles"], least_bytes, peaks
    )
