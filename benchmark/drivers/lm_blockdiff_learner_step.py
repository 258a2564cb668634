"""Traffic ``lm_blockdiff_learner_step``: ``lm_latent_learner_step``'s cell
for a decoder under block diffusion whose action is a denoising step. The
whole of that driver's ``run`` (the window, the timing, the stall watch,
the attention-backend check, the ``moe_*`` counters, the routing's
labelling, the comparison by the four gaps under the cell's four
``limits``) runs as it stands, over this file's :class:`Cell`; what
differs, and why this is a file of its own:

- the batch has two time axes (``lib/seeded_sdar.py``): ``unroll_length``
  counts *steps*, which is what ``learner_env_steps_per_s`` counts; the
  tokens are ``D (unroll_length / S + 1)``, and the driver prints tokens a
  second beside the step's rate. The observation is a dict, so the
  parameter shapes come from a call of that form;
- the step reports no ``mtp_loss``: that driver follows the term on both
  sides, so both give it as zero (``lm_mhc_learner_step.NoModule``, the
  reference in ``reference/<configuration>.py``);
- the model's counters join the step's and are printed on a ``[sdar]``
  line beside ``lib/counts_sdar.py``'s count of the same from the batch's
  ``done`` and ``reveal_step``: ``sdar_counts_differ``, exactly 0;
- the reference's follower waits for its gradient's program and frees a
  step's gradient once its update is dispatched
  (``lm_eva_learner_step.Follower``).

Workload file keys: those of ``lm_latent_learner_step``.
"""

from __future__ import annotations

import importlib

import jax
import numpy as np

from benchmark.drivers import lm_eva_learner_step as eva
from benchmark.drivers import lm_latent_learner_step as latent
from benchmark.drivers import lm_mhc_learner_step as mhc
from benchmark.drivers.lm_learner_step import Recording
from benchmark.lib import counts_sdar, program, seeded_sdar


class Counting(mhc.NoModule):
    """``NoModule`` with the model's counters beside the expert layers',
    and what they are held to."""

    def __init__(self, step):
        super().__init__(step)
        self.counted = None  # from the batch's done and reveal_step

    def counters(self) -> dict:
        counters = self._step.counters()
        counters.update(
            {k: float(self._step.last[k]) for k in counts_sdar.COUNTERS}
        )
        return counters

    def describe(self) -> str:
        counters = self.counters()
        return (
            "[sdar] of the last step: " + ", ".join(
                f"{k} {counters[k]:.12g}" for k in counts_sdar.COUNTERS
            ) + f"; counted from the batch's done and reveal_step "
            f"{self.counted}"
        )

    def counts_differ(self) -> int:
        counters = self.counters()
        return sum(
            int(counters[k] != self.counted[k]) for k in counts_sdar.COUNTERS
        )


class Cell(latent.Cell):
    def __init__(self, cell: dict, config: dict, devices):
        # not that class's: the parameter shapes come from a dict
        # observation
        if len(devices) != 1:
            raise ValueError("lm_blockdiff_learner_step runs one chip's share")
        self.cell, self.config, self.devices = cell, config, list(devices)
        self.T = cell["unroll_length"]
        self.B = cell["batch_per_chip"]
        self.model = config["model"]["kwargs"]
        self.net = program.build_model(config)
        self.shapes = seeded_sdar.param_shapes(self.net)
        self.optimizer = program.build_optimizer(config)
        apply_fn = program.resolve(config["apply_factory"])(self.net)
        self.step = Counting(Recording(
            program.resolve(config["step_factory"])(
                apply_fn, self.optimizer, program.loss_config(config),
                mesh=None, donate=True,
            )
        ))
        self.loss_fn = importlib.import_module(
            f"benchmark.reference.{config['reference']}"
        ).loss_fn
        self._followers = {}
        self.loads = jax.jit(program.resolve(config["router_loads_factory"])(
            self.net.clone(moe_buffer_rows=None)
        ))
        self.held = tuple(self.model["experts_held"])
        self._perms = {}

    def follower(self, precision: str) -> eva.Follower:
        if precision not in self._followers:
            self._followers[precision] = eva.Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        params = seeded_sdar.make_params(self.shapes, seed, self.model)
        if seed in self._perms:
            return seeded_sdar.permute_routers(params, self._perms[seed])
        params, self._perms[seed], before, after = seeded_sdar.balance_held(
            params, self.loads, batch, self.held, seed
        )
        print(f"[balance] seed {seed}: assignments held by layer as seeded "
              f"{before} (sum {sum(before)}), as labelled {after} (sum "
              f"{sum(after)})", flush=True)
        return params

    def batch(self, seed: int):
        batch = seeded_sdar.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            tiles=self.cell.get("attention_tiles"),
        )
        self.step.counted = counts_sdar.counts(
            self.model, np.asarray(batch["done"]),
            np.asarray(batch["obs"]["reveal_step"]),
        )
        return batch


class calibration(latent.calibration):
    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)


def run(ctx) -> dict:
    """``lm_latent_learner_step.run``, which builds its cell by the name
    ``Cell`` of its own module: this file's class stands there for the
    length of the call, and its holds join the verdict after."""
    built = []

    def build(*args):
        built.append(Cell(*args))
        return built[0]

    theirs, latent.Cell = latent.Cell, build
    try:
        out = latent.run(ctx)
    finally:
        latent.Cell = theirs
    c = built[0]
    print(c.step.describe(), flush=True)
    tokens = c.step.counted["blockdiff_scored_tokens"]
    rate = out["end_to_end"]["learner_env_steps_per_s"]
    print(f"[tokens] {tokens} token-actions in {c.T * c.B} steps: "
          f"{rate * tokens / (c.T * c.B):.1f} tokens/s at {rate:.1f} "
          f"steps/s", flush=True)
    ctx.verdict.hold("sdar_counts_differ", c.step.counts_differ(), 0,
                     exact=True)
    ctx.verdict.hold("moe_spills", c.step.counters()["moe_spills"], 0,
                     exact=True)
    return out
