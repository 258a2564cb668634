"""Traffic ``lm_eva_learner_step``: ``lm_latent_learner_step``'s cell for a
decoder with chunk-summary attention, eight prediction heads and no expert
layer at all. The whole of that driver's ``run`` (the window, the timing,
the stall watch, the attention-backend check, the comparison by the four
gaps under the cell's four ``limits``, the ``mtp_loss`` of both sides)
runs as it stands, over this file's :class:`Cell`; what differs, and why
this is a file of its own:

- no routing to count and no experts to label: the weights are
  ``lib/seeded_eva.py``'s as seeded, ``[balance]`` is not printed and
  ``[routing]`` reads empty lists;
- the ``moe_*`` counters that driver prints and holds are what they are in
  a model without experts, zero, on both sides (:class:`NoExperts`);
- the attention's own counters (``eva_local_pairs``,
  ``eva_summary_pairs``, ``eva_chunks_cut``) join the step's counters and
  are printed with the further heads' ``mtp_loss`` / ``mtp_positions`` on
  an ``[eva]`` line, beside ``lib/counts_eva.py``'s count of the same
  pairs from the batch's boundaries;
- the episode boundaries are drawn for the tiles of both attention calls
  (``lib/seeded_eva.py``);
- the reference's follower frees a step's gradient once its update is
  dispatched (:class:`Follower`).

Workload file keys: those of ``lm_latent_learner_step``.
"""

from __future__ import annotations

import importlib

import jax
import numpy as np

from benchmark.drivers import lm_latent_learner_step as latent
from benchmark.drivers.lm_learner_step import COUNTERS
from benchmark.drivers import lm_mhc_learner_step as mhc
from benchmark.lib import counts_eva, program, seeded_eva

EVA_COUNTERS = ("eva_local_pairs", "eva_summary_pairs", "eva_chunks_cut")


class NoExperts:
    """The step of a model without expert layers, keeping the metrics of
    its last call; the expert layers' counters as what they are there,
    zero."""

    def __init__(self, step):
        self._step, self.last = step, None
        self.expected = {}  # the last batch's pairs and tiles, every block

    def __call__(self, state, batch):
        state, self.last = self._step(state, batch)
        return state, self.last

    def counters(self) -> dict:
        counters = dict.fromkeys(COUNTERS, 0.0)
        counters.update({k: float(self.last[k]) for k in EVA_COUNTERS})
        print("[eva] of the last step, every block: " + ", ".join(
            f"{k} {float(self.last[k]):.9g}"
            for k in EVA_COUNTERS + latent.MTP_COUNTERS
        ) + f"; counted from the boundaries: {self.expected}", flush=True)
        return counters

    def overflow(self) -> float:
        return 0.0


class Follower(mhc.Follower):
    """That driver's follower (its gradient's program waited for), which
    also frees a step's gradient once its update is dispatched.
    ``lib/reference_latent.py:Follower.follow`` keeps the first step's
    gradient alive to its end (its list ``leaves``), and at 821M
    parameters a third tree of 3.3 GB beside the weights and the next
    step's gradient leaves the program's 6.2-6.9 GB of temporaries no room
    (``RESOURCE_EXHAUSTED`` when the second step's program is loaded, PR
    40's first chip run). Nothing reads a step's gradient after its
    update; what the update could take as a donation is gone already."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        update = self._update

        def then_free(params, nu, g, scale):
            out = update(params, nu, g, scale)
            for leaf in jax.tree_util.tree_leaves(g):
                if not leaf.is_deleted():
                    leaf.delete()
            return out

        self._update = then_free


class Cell(latent.Cell):
    def __init__(self, cell: dict, config: dict, devices):
        # not that class's: it builds the routing's program
        if len(devices) != 1:
            raise ValueError("lm_eva_learner_step runs one chip's share")
        self.cell, self.config, self.devices = cell, config, list(devices)
        self.T = cell["unroll_length"]
        self.B = cell["batch_per_chip"]
        self.model = config["model"]["kwargs"]
        self.net = program.build_model(config)
        self.shapes = seeded_eva.param_shapes(self.net)
        self.optimizer = program.build_optimizer(config)
        apply_fn = program.resolve(config["apply_factory"])(self.net)
        self.step = NoExperts(program.resolve(config["step_factory"])(
            apply_fn, self.optimizer, program.loss_config(config),
            mesh=None, donate=True,
        ))
        self.loss_fn = importlib.import_module(
            f"benchmark.reference.{config['reference']}"
        ).loss_fn
        self._followers = {}

    def follower(self, precision: str) -> Follower:
        if precision not in self._followers:
            self._followers[precision] = Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        return seeded_eva.make_params(
            self.shapes, seed, self.model, self.config["seeding"]
        )

    def held_by_layer(self, params, batch) -> list:
        return []

    def batch(self, seed: int):
        batch = seeded_eva.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            tiles=self.cell.get("attention_tiles"),
        )
        n, done = counts_eva.blocks(self.model), np.asarray(batch["done"])
        columns = [
            counts_eva.attention_counts(self.model, done[:, b])
            for b in range(self.B)
        ]
        self.step.expected = {
            name: n * sum(c[name] for c in columns) for name in columns[0]
        }
        return batch


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
