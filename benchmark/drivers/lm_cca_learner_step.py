"""Traffic ``lm_cca_learner_step``: ``lm_latent_learner_step``'s cell for a
decoder of compressed convolutional attention and top-1 experts behind an
MLP router whose state goes through the depth. The whole of that driver's
``run`` (the window, the timing, the stall watch, the attention-backend
check, the ``moe_*`` counters, the routing's labelling, the comparison by
the four gaps under the cell's four ``limits``) runs as it stands, over
this file's :class:`Cell`; what differs, and why this is a file of its
own:

- the attention's, the router's and the skeleton's own leaves are seeded
  at the scales the configuration states, and the experts held are
  labelled over the router's *expert* columns alone, the column that is no
  expert staying last (``lib/seeded_cca.py``); the boundaries are drawn
  with ``done[0]`` false and for the count of tiles the cell states, a
  count only a draw with a boundary leaves;
- the step reports no ``mtp_loss``: that driver follows the term on both
  sides, so both give it as zero (``lm_mhc_learner_step.NoModule``, the
  reference in ``reference/<configuration>.py``);
- the model's counters join the step's and are printed on a ``[zaya]``
  line: ``cca_taps_cut`` beside ``lib/counts_cca.py``'s count of the same
  from the batch's ``done``, which must be equal; ``moe_tokens_skipped``
  beside the router's own count of its choices past the experts
  (``moe_router_load``, by a forward pass of its own when the window opens
  and closes: other weights than the last step's by one update, so beside
  it and not held to it) and held to the step's other counters, which it
  must agree with (with one expert a token, the tokens no held expert
  served are the assignments not held, and the skipped are among them):
  ``zaya_counts_differ``, exactly 0; ``moe_gate_mean`` and
  ``router_state_rms``;
- the reference's follower waits for its gradient's program and frees a
  step's gradient once its update is dispatched
  (``lm_eva_learner_step.Follower``).

Workload file keys: those of ``lm_latent_learner_step``.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import lm_eva_learner_step as eva
from benchmark.drivers import lm_latent_learner_step as latent
from benchmark.drivers import lm_mhc_learner_step as mhc
from benchmark.lib import counts_cca, seeded_cca

ZAYA_COUNTERS = ("cca_taps_cut", "moe_tokens_skipped", "moe_gate_mean",
                 "router_state_rms")


class Counting(mhc.NoModule):
    """``NoModule`` with the model's counters beside the expert layers',
    and what they are held to."""

    def __init__(self, step):
        super().__init__(step)
        self.taps_cut = None  # counted from the batch's boundaries
        self.router_skipped = []  # the router's own count, open and close

    def counters(self) -> dict:
        counters = self._step.counters()
        counters.update(
            {k: float(self._step.last[k]) for k in ZAYA_COUNTERS}
        )
        return counters

    def describe(self) -> str:
        counters = self.counters()
        return (
            "[zaya] of the last step, every layer: " + ", ".join(
                f"{k} {counters[k]:.9g}" for k in ZAYA_COUNTERS
            ) + f"; cca_taps_cut counted from the boundaries "
            f"{self.taps_cut}; the router's own count of its choices past "
            f"the experts when the window opened and closed "
            f"{self.router_skipped}"
        )

    def counts_differ(self) -> int:
        c = self.counters()
        unserved = c["moe_assignments_total"] - c["moe_assignments_held"]
        return int(c["cca_taps_cut"] != self.taps_cut) + int(
            c["moe_tokens_unserved"] != unserved
            or not 0 < c["moe_tokens_skipped"] <= unserved
        )


class Cell(latent.Cell):
    def __init__(self, cell: dict, config: dict, devices):
        super().__init__(cell, config, devices)
        self.step = Counting(self.step)
        self.skip = self.model["router"]["skip_choices"]

    def follower(self, precision: str) -> eva.Follower:
        if precision not in self._followers:
            self._followers[precision] = eva.Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        """That driver's labelled weights over this file's seeding and
        this router's columns."""
        params = seeded_cca.make_params(
            self.shapes, seed, self.model, self.config["seeding"]
        )
        if seed in self._perms:
            return seeded_cca.permute_routers(params, self._perms[seed])
        params, self._perms[seed], before, after = seeded_cca.balance_held(
            params, self.loads, batch, self.held, self.skip, seed
        )
        print(f"[balance] seed {seed}: assignments held by layer as seeded "
              f"{before} (sum {sum(before)}), as labelled {after} (sum "
              f"{sum(after)})", flush=True)
        return params

    def held_by_layer(self, params, batch) -> list:
        first, count = self.held
        loads = np.asarray(self.loads(params, batch["obs"], batch["done"]))
        self.step.router_skipped.append(int(loads[:, -self.skip:].sum()))
        return [int(x) for x in loads[:, first:first + count].sum(axis=1)]

    def batch(self, seed: int):
        batch = seeded_cca.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            tiles=self.cell.get("attention_tiles"),
        )
        done = np.asarray(batch["done"])
        self.step.taps_cut = sum(
            counts_cca.taps_cut(self.model, done[:, b])
            for b in range(self.B)
        )
        return batch


class calibration(latent.calibration):
    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)


def run(ctx) -> dict:
    """``lm_latent_learner_step.run``, which builds its cell by the name
    ``Cell`` of its own module: this file's class stands there for the
    length of the call, and its hold joins the verdict after."""
    built = []

    def build(*args):
        built.append(Cell(*args))
        return built[0]

    theirs, latent.Cell = latent.Cell, build
    try:
        out = latent.run(ctx)
    finally:
        latent.Cell = theirs
    step = built[0].step
    print(step.describe(), flush=True)
    ctx.verdict.hold("zaya_counts_differ", step.counts_differ(), 0, exact=True)
    ctx.verdict.hold("moe_spills", step.counters()["moe_spills"], 0,
                     exact=True)
    return out
