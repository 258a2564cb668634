"""Traffic ``lm_kda_learner_step``: ``lm_latent_learner_step``'s cell for a
decoder whose blocks mix tokens by the gated delta rule and hand a state
from call to call. The whole of that driver's ``run`` (the window, the
timing, the stall watch, the attention-backend check, the ``moe_*``
counters, the routing's labelling, the comparison by the four gaps under
the cell's four ``limits``) runs as it stands, over this file's
:class:`Cell`; what differs, and why this is a file of its own:

- the batch has a seeded, non-zero ``core_state`` and its boundaries are
  drawn for the softmax layers' tiles alone; the delta rule's own leaves
  are seeded at the scales the configuration states
  (``lib/seeded_kda.py``);
- the step reports no ``mtp_loss``: that driver follows the term on both
  sides, so both give it as zero (``lm_mhc_learner_step.NoModule``, the
  reference in ``reference/<configuration>.py``);
- the rule's counters (``kda_state_resets``, ``kda_chunks_cut``,
  ``kda_log_decay_min``, ``kda_state_rms``) join the step's counters and
  are printed on a ``[kda]`` line, the first two beside
  ``lib/counts_kda.py``'s count of the same from the batch's ``done``,
  which must be equal (``kda_counts_differ``, exactly 0);
- the path the recurrence ran is held to the one the cell names
  (``recurrent_path``), and the calls a trace of the step makes to the
  count the cell states (``recurrent_calls``: a repeated entry's blocks
  are one scan, traced once): ``recurrent_path_differs``, exactly 0;
- the reference's follower waits for its gradient's program and frees a
  step's gradient once its update is dispatched
  (``lm_eva_learner_step.Follower``).

Workload file keys: those of ``lm_latent_learner_step``, and
``recurrent_path``, ``recurrent_calls``.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmark.drivers import lm_eva_learner_step as eva
from benchmark.drivers import lm_latent_learner_step as latent
from benchmark.drivers import lm_mhc_learner_step as mhc
from benchmark.lib import counts_kda, program, seeded_kda, seeded_latent

KDA_COUNTED = ("kda_state_resets", "kda_chunks_cut")
KDA_GAUGES = ("kda_log_decay_min", "kda_state_rms")


def paths_traced() -> dict:
    """Calls of the recurrence traced so far in this process, by path."""
    registry = program.resolve(
        "moolib_tpu.telemetry.global_telemetry"
    )().registry
    return {
        path: registry.value("recurrent_mix_calls_traced_total", path=path)
        or 0 for path in ("chunked",)
    }


class Carrying(mhc.NoModule):
    """``NoModule`` with the rule's counters beside the expert layers',
    and what the batch's boundaries say they should be."""

    def __init__(self, step):
        super().__init__(step)
        self.expected = {}

    def counters(self) -> dict:
        counters = self._step.counters()
        last = self._step.last
        counters.update(
            {k: float(last[k]) for k in KDA_COUNTED + KDA_GAUGES}
        )
        print("[kda] of the last step, every block: " + ", ".join(
            f"{k} {counters[k]:.9g}" for k in KDA_COUNTED + KDA_GAUGES
        ) + f"; counted from the boundaries: {self.expected}", flush=True)
        return counters

    def counts_differ(self) -> int:
        last = self._step.last
        return sum(
            int(float(last[k]) != float(v)) for k, v in self.expected.items()
        )


class Cell(latent.Cell):
    def __init__(self, cell: dict, config: dict, devices):
        super().__init__(cell, config, devices)
        self.step = Carrying(self.step)
        self.state_shapes = jax.eval_shape(
            lambda: self.net.initial_state(self.B)
        )

    def follower(self, precision: str) -> eva.Follower:
        if precision not in self._followers:
            self._followers[precision] = eva.Follower(
                self.loss_fn, self.config, precision, self.devices[0]
            )
        return self._followers[precision]

    def params(self, seed: int, batch):
        """That driver's labelled weights over this file's seeding."""
        params = seeded_kda.make_params(
            self.shapes, seed, self.model, self.config["seeding"]
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

    def batch(self, seed: int):
        batch = seeded_kda.make_learn_batch(
            seed, self.config, self.T, self.B, self.cell["done_rate"],
            self.state_shapes, tiles=self.cell.get("attention_tiles"),
        )
        done = np.asarray(batch["done"])
        columns = [
            counts_kda.boundary_counts(self.model, done[:, b])
            for b in range(self.B)
        ]
        self.step.expected = {
            name: sum(c[name] for c in columns) for name in columns[0]
        }
        return batch

    def first_steps(self, state, batch):
        """That driver's first steps, then the two holds that are this
        cell's own: they read what the step's trace and its last call
        left."""
        before = paths_traced()
        out = super().first_steps(state, batch)
        ran = {
            path: int(n - before[path]) for path, n in paths_traced().items()
        }
        want = self.cell["recurrent_path"]
        print(f"[recurrent] calls traced into the step, by path: {ran}; "
              f"cell wants {self.cell['recurrent_calls']} on "
              f"{want!r}", flush=True)
        self.path_differs = sum(
            n for path, n in ran.items() if path != want
        ) + abs(ran.get(want, 0) - self.cell["recurrent_calls"])
        return out


class calibration(latent.calibration):
    def __init__(self, cell, config, devices):
        self.c = Cell(cell, config, devices)


def run(ctx) -> dict:
    """``lm_latent_learner_step.run``, which builds its cell by the name
    ``Cell`` of its own module: this file's class stands there for the
    length of the call, and its two holds join the verdict after."""
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
    ctx.verdict.hold("recurrent_path_differs", c.path_differs, 0, exact=True)
    ctx.verdict.hold("kda_counts_differ", c.step.counts_differ(), 0,
                     exact=True)
    return out
