"""The comparison that decides ``correct`` for training: the program's first
steps against the plain reference's, number by number, each with its limit.

Numbers (all gaps are relative, 0 is perfect):

- ``loss_gap_first``: |loss - reference loss| over |reference loss| at the
  seeded weights (step 0). Low precision hardly moves it; it is here to
  catch a part of the batch left out or averaged wrongly (the seeded
  batch's columns differ in reward scale for that).
- ``loss_gap_later``: the worst of the same over the later steps, over the
  largest |reference loss| of all steps followed (a loss can pass near
  zero). The first steps from seeded weights are large (the clip at 40 is
  active) and amplify rounding, so this one swings; it is here to catch a
  step that returns its state unchanged, whose loss then stays put.
- ``grad_leaf_gap``: the first gradient as the optimizer gets it (after the
  clip, after any exchange between chips). Only the magnitude of each
  element can be read back out of the RMSProp state after one step (nu =
  (1 - decay) g^2), so per leaf the number is the norm of the difference of
  the two sides' magnitudes, over the larger of the reference's norm of that
  leaf and of its median leaf (some gradients are all but zero); the worst
  leaf counts. The gap between the two norms, which this replaces, sees only
  a bias in size and not the rounding noise, and did not separate bfloat16
  from fp8 (readings in PERF.md). This is the number a lower precision
  moves.
- ``change_leaf_gap``: per leaf, the gap between the norm of the program's
  change of the parameters over the steps followed and the reference's, on
  the same scale; the worst leaf. Here to catch a step that returns its
  state unchanged (gap 1).
"""

from __future__ import annotations

import numpy as np


def _norms(leaves) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.ravel(x)) for x in leaves])


def _scale(reference_norms: np.ndarray) -> np.ndarray:
    return np.maximum(
        np.maximum(reference_norms, np.median(reference_norms)), 1e-30
    )


def worst_leaf_gap(program, reference) -> float:
    """Per leaf |norm - reference norm| over the reference's norm of that
    leaf or of the median leaf, whichever is larger; the worst leaf."""
    p, r = _norms(program), _norms(reference)
    return float(np.max(np.abs(p - r) / _scale(r)))


def worst_leaf_error(program, reference) -> float:
    """Per leaf the norm of the difference, on the same scale."""
    r = _norms(reference)
    d = _norms([np.asarray(a) - np.asarray(b)
                for a, b in zip(program, reference)])
    return float(np.max(d / _scale(r)))


def training_numbers(program: dict, reference: dict) -> dict:
    """Both sides: ``losses`` (list), ``grad_abs`` and ``change`` (lists of
    per-leaf arrays in the parameter tree's flattening order)."""
    steps = min(len(program["losses"]), len(reference["losses"]))
    p, r = program["losses"][:steps], reference["losses"][:steps]
    scale = max(max(abs(x) for x in r), 1e-30)
    return {
        "loss_gap_first": abs(p[0] - r[0]) / max(abs(r[0]), 1e-30),
        "loss_gap_later": max(
            (abs(a - b) / scale for a, b in zip(p[1:], r[1:])), default=0.0
        ),
        "grad_leaf_gap": worst_leaf_error(
            program["grad_abs"], reference["grad_abs"]
        ),
        "change_leaf_gap": worst_leaf_gap(
            program["change"], reference["change"]
        ),
    }


class Verdict:
    """Collects every number compared beside its limit and prints each."""

    def __init__(self):
        self.rows = []

    def hold(self, name: str, value, limit, *, exact: bool = False) -> bool:
        """``value`` has to be finite and at most ``limit`` (equal to it,
        for an exact comparison)."""
        value = float(value)
        ok = bool(np.isfinite(value)) and (
            value == limit if exact else value <= limit
        )
        self.rows.append((name, value, limit, ok))
        print(
            f"[compare] {name} = {value:.6g}  "
            f"{'==' if exact else '<='} {limit:g}  "
            f"{'ok' if ok else 'NOT OK'}", flush=True,
        )
        return ok

    def hold_all(self, numbers: dict, limits: dict) -> None:
        for name, value in numbers.items():
            self.hold(name, value, limits[name])

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(ok for *_, ok in self.rows)
