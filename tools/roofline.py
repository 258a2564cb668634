"""Analytic roofline for the ImpalaNet train step: where does the time go,
and what MFU is even attainable on a 128x128-lane MXU?

Per layer this prints (a) useful model FLOPs, (b) the naive-mapping MXU tile
efficiency — a conv is an implicit matmul with contraction K = kh*kw*c_in
and output lanes N = c_out, and the systolic array pads both to multiples of
128 — and (c) activation bytes moved (bf16), giving an HBM time floor. The
point of the table: ImpalaNet's 16/32-channel convs cap useful-MAC density
at 3.5-19% per layer, so a measured MFU in the low teens means the MXU is
effectively saturated for this architecture, not idle. (The reference has no
comparable accounting — its perf story is env-steps/s alone, reference:
README.md:34-37.)

The layer walk itself comes from moolib_tpu.utils.flops.impala_layer_walk —
the same source the benchmark's MFU denominator uses, so this table cannot
drift from what bench.py measures.

Usage: python tools/roofline.py [B] [T]   (defaults B=256 T=20)
Pure Python — runs anywhere, no jax/TPU needed.
"""

from __future__ import annotations

import math
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from moolib_tpu.utils.flops import TRAIN_FLOPS_MULTIPLIER, impala_layer_walk  # noqa: E402

MXU = 128  # systolic array is MXU x MXU lanes
BF16 = 2  # bytes
PEAK = 197e12  # v5e bf16 FLOP/s
HBM = 819e9  # v5e bytes/s
# 76,377 env-steps/s at T=20, B=256: taken on a harness that has since been
# removed (CHANGES.md, PR 21), older than most of the code; not re-measured.
MEASURED_MS_B256 = 67.0


def tile_eff(k: int, n: int) -> float:
    """Useful-MAC fraction of MXU tiles for a (M,K)x(K,N) matmul, M large:
    both K and N pad up to multiples of 128."""
    return (k * n) / (math.ceil(k / MXU) * MXU * math.ceil(n / MXU) * MXU)


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    T = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    frames = (T + 1) * B

    rows = list(impala_layer_walk())
    tot_f = sum(r[1] for r in rows)
    tot_padded = sum(r[1] / tile_eff(r[2], r[3]) for r in rows)
    act_bytes = sum(r[4] * BF16 for r in rows)

    print(f"{'layer':38s} {'MFLOP/frm':>9s} {'share':>6s} {'K':>5s} {'N':>4s} "
          f"{'tile_eff':>8s} {'act_KB':>7s}")
    for name, f, k, n, elems in rows:
        print(f"{name:38s} {f / 1e6:9.2f} {f / tot_f:6.1%} {k:5d} {n:4d} "
              f"{tile_eff(k, n):8.1%} {elems * BF16 / 1024:7.0f}")

    train_f = TRAIN_FLOPS_MULTIPLIER * frames * tot_f
    naive_ceiling = tot_f / tot_padded
    # fwd reads each layer's input (~= previous layer's output) and writes
    # its activation; bwd re-reads the activation and writes a grad of the
    # same shape -> ~4x fwd activation bytes. Weight/grad-weight traffic is
    # omitted (params are ~1.6MB total, noise next to activations here).
    traffic = 4 * frames * act_bytes
    print(f"\nper-frame useful fwd FLOPs:    {tot_f / 1e6:.1f} M")
    print(f"train step ({frames} frames):  {train_f / 1e12:.2f} TFLOP useful")
    print(f"naive-mapping MXU ceiling:     {naive_ceiling:.1%} MFU "
          f"(padded tiles: {TRAIN_FLOPS_MULTIPLIER * frames * tot_padded / 1e12:.1f}"
          " TFLOP-equiv)")
    print(f"MXU time floor @197T bf16:     {train_f / PEAK * 1e3:.1f} ms "
          f"(100% MFU), {train_f / PEAK / naive_ceiling * 1e3:.1f} ms naive")
    print(f"activation traffic (~4x fwd):  {traffic / 1e9:.1f} GB "
          f"-> HBM floor {traffic / HBM * 1e3:.1f} ms @819GB/s "
          "(input reads + act writes + bwd re-reads + grad writes; "
          "weight traffic omitted)")
    if (B, T) == (256, 20):
        print(f"\nreading: measured {MEASURED_MS_B256:.0f} ms/step "
              "(removed round-3 harness, B=256) sits between the naive-mapping MXU "
              "bound and the HBM floor -> XLA's conv packing already beats "
              "naive im2col on these narrow channels; the remaining gap is "
              "lane padding, which is architectural.")


if __name__ == "__main__":
    main()
