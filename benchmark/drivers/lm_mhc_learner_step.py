"""Traffic ``lm_mhc_learner_step``: ``lm_latent_learner_step``'s cell for a
decoder whose blocks sit on a residual skeleton with several streams and
which has no prediction module. The whole of that driver's ``run`` (the
window, the timing, the stall watch, the attention-backend check, the
``moe_*`` counters, the comparison by the four gaps under the cell's four
``limits``) runs as it stands, over this file's :class:`Cell`; what
differs, and why this is a file of its own:

- the mixing's parameters (``phi``, ``b``, ``alpha`` under every block's
  ``hc_attn`` / ``hc_mlp``) are seeded by ``lib/seeded_mhc.py`` over the
  tree ``lib/seeded_latent.py`` makes, at the scales the configuration
  states under ``seeding``;
- the step reports no ``mtp_loss``: the model has no module. That
  driver follows the term on both sides, so here both sides give it as
  zero (the reference in ``reference/<configuration>.py``, the program's
  through :class:`NoModule`), and its ``[mtp]`` line reads zeros;
- the mixing's counters (``hc_row_sum_gap``, ``hc_col_sum_gap``,
  ``hc_res_clamped``) join the step's counters and are printed on an
  ``[mhc]`` line;
- the reference's follower waits for its gradient's program
  (:class:`Follower`).

Workload file keys: those of ``lm_latent_learner_step``.
"""

from __future__ import annotations

import jax

from benchmark.drivers import lm_latent_learner_step as latent
from benchmark.lib import reference_latent, seeded_latent, seeded_mhc

MHC_COUNTERS = ("hc_row_sum_gap", "hc_col_sum_gap", "hc_res_clamped")
NO_MODULE = {"mtp_loss": 0.0, "mtp_positions": 0.0}


class NoModule:
    """The recorded step of a model without a prediction module, with the
    module's term and count as what they are there, zero, for the driver
    that follows them, and with the mixing's counters beside the expert
    layers'."""

    def __init__(self, step):
        self._step = step

    def __call__(self, state, batch):
        state, metrics = self._step(state, batch)
        return state, dict(metrics, **NO_MODULE)

    @property
    def last(self) -> dict:
        return dict(self._step.last, **NO_MODULE)

    def counters(self) -> dict:
        counters = self._step.counters()
        counters.update(
            {k: float(self._step.last[k]) for k in MHC_COUNTERS}
        )
        print("[mhc] of the last step's remix matrices, over tokens and "
              "sublayers: " + ", ".join(
                  f"{k} {counters[k]:.6g}" for k in MHC_COUNTERS
              ), flush=True)
        return counters

    def overflow(self) -> float:
        return self._step.overflow()


class Follower(reference_latent.Follower):
    """That file's follower with its gradient's program waited for: at
    759M parameters the next array put on the device (RMSProp's slot, 3
    GB) otherwise meets the program still running with its 6 GB of
    temporaries beside two trees, and the fp8 control's program, which is
    larger, then runs the chip out of memory."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        grad = self._grad
        self._grad = lambda params, batch: jax.block_until_ready(
            grad(params, batch)
        )


class Cell(latent.Cell):
    def __init__(self, cell: dict, config: dict, devices):
        super().__init__(cell, config, devices)
        self.step = NoModule(self.step)

    def follower(self, precision: str) -> Follower:
        if precision not in self._followers:
            self._followers[precision] = Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        """That driver's seeded and labelled weights, with the mixing's
        leaves seeded over them before the labelling is found."""
        seeding = self.config["seeding"]
        params = seeded_mhc.seed_mixing(
            seeded_latent.make_params(
                self.shapes, seed, self.model,
                seeding["correction_bias_scale"],
            ),
            seed, self.model["residual"]["streams"], seeding["hc_b_scale"],
            seeding["hc_res_diagonal"],
        )
        if seed in self._perms:
            return seeded_latent.permute_routers(params, self._perms[seed])
        params, self._perms[seed], before, after = (
            seeded_latent.balance_held(
                params, self.loads, batch, self.held, seed
            )
        )
        print(f"[balance] seed {seed}: assignments held by layer as seeded "
              f"{before} (sum {sum(before)}), as labelled {after} (sum "
              f"{sum(after)})", flush=True)
        return params


class calibration(latent.calibration):
    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)


def run(ctx) -> dict:
    """``lm_latent_learner_step.run``, which builds its cell by the name
    ``Cell`` of its own module: this file's class stands there for the
    length of the call."""
    theirs, latent.Cell = latent.Cell, Cell
    try:
        return latent.run(ctx)
    finally:
        latent.Cell = theirs
