"""The plain side of the training comparison for a configuration whose
step has a loss term of its own (a multi-token-prediction module's
cross-entropy) and whose size forbids reading whole trees back: what
``lib/reference_train.py``'s ``Follower`` and ``lib/program.py``'s
``first_steps`` are to the other cells, leaf by leaf.

The configuration's ``reference/<name>.py`` supplies ``loss_fn(params,
batch, loss, cast) -> (total, {"mtp_loss", "mtp_positions"})``, the whole
step's loss in blocks that fit; clip and RMSProp are written out here as
``Follower`` has them. Nothing of the program is imported.

**Leaf by leaf.** ``compare.training_numbers`` wants, of both sides, the
first gradient's magnitudes and the parameters' change as lists of float64
arrays: three whole trees a side (37 GB of host memory at 706M
parameters). What its two leaf numbers read is less: of the gradient, per
leaf the norm of the two sides' difference and the reference's norm; of
the change, per leaf each side's norm. So the side that runs first (the
program, before the window) keeps its first gradient's magnitudes as
float32 on the host (one tree, 2.8 GB) and its change's norms; the
reference, once it has its own first gradient, reduces leaf by leaf
against what was kept. :func:`numbers` then applies ``lib/compare.py``'s
scale (a leaf's reference norm or the median leaf's, whichever is larger)
and gives the four numbers under the names every cell's limits use.
"""

from __future__ import annotations

import resource

import jax
import jax.numpy as jnp
import numpy as np

from .compare import _scale
from .reference_train import CASTS


def leaf_norm(x) -> float:
    return float(np.linalg.norm(np.ravel(x)))


def to_host(x, dtype=None) -> np.ndarray:
    """A device array on the host, by way of a copy on the device: read
    directly, jax keeps the host value beside the array for as long as
    the array lives, and a tree's worth of those is what this file is
    here to avoid (33 GB resident before this, PR 31's first chip run)."""
    return np.asarray(jnp.copy(x), dtype)


def host_peak_gb() -> float:
    """The process's peak resident memory so far (Linux: KiB)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def program_first_steps(step, state, batch, steps: int, decay: float):
    """The program's first ``steps`` steps through ``step(state, batch) ->
    (state, metrics)``, the call the window uses. Returns the state to go
    on from and its side of the comparison: each step's ``total_loss`` and
    ``mtp_loss``, the first gradient's magnitudes as RMSProp got them (out
    of its state after one step: nu = (1 - decay) g^2) as float32 on the
    host, and the norm of every leaf's change."""
    from .program import second_moments

    start = [to_host(x) for x in jax.tree_util.tree_leaves(state.params)]
    losses, mtp_losses, grad_abs = [], [], None
    for i in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["total_loss"]))
        mtp_losses.append(float(metrics["mtp_loss"]))
        if i == 0:
            grad_abs = [
                np.sqrt(to_host(x, np.float64) / (1.0 - decay)).astype(
                    np.float32
                )
                for x in jax.tree_util.tree_leaves(
                    second_moments(state.opt_state)
                )
            ]
    change_norms = [
        leaf_norm(to_host(a, np.float64) - b)
        for a, b in zip(jax.tree_util.tree_leaves(state.params), start)
    ]
    return state, {
        "losses": losses, "mtp_losses": mtp_losses, "grad_abs": grad_abs,
        "change_norms": change_norms,
    }


class Follower:
    """Follows the first steps from the same weights on the same batch:
    the reference's loss and gradient, then ``clip_by_global_norm`` and
    RMSProp as optax defines them,

        nu <- decay nu + (1 - decay) g^2,  p <- p - lr g / sqrt(nu + eps),

    with nu starting at 0."""

    def __init__(self, loss_fn, config, precision="float32", device=None):
        opt = config["optimizer"]
        self.device = device
        cast = CASTS[precision]
        loss = dict(config["loss"])

        def grad(params, batch):
            (total, parts), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, loss, cast
            )
            norm = jnp.sqrt(sum(
                jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)
            ))
            scale = jnp.where(
                norm < opt["grad_clip"], 1.0, opt["grad_clip"] / norm
            )
            return total, parts["mtp_loss"], scale, g

        def update(params, nu, g, scale):
            g = jax.tree_util.tree_map(lambda x: x * scale, g)
            nu = jax.tree_util.tree_map(
                lambda n, x: opt["decay"] * n + (1 - opt["decay"]) * x * x,
                nu, g,
            )
            params = jax.tree_util.tree_map(
                lambda p, x, n: p
                - opt["learning_rate"] * x / jnp.sqrt(n + opt["eps"]),
                params, g, nu,
            )
            return params, nu

        self._grad = jax.jit(grad)
        self._update = jax.jit(update, donate_argnums=(0, 1, 2))

    def _put(self, tree):
        return tree if self.device is None else jax.device_put(
            tree, self.device
        )

    def follow(self, make_params, batch, steps: int, against=None):
        """``steps`` optimizer steps from ``make_params()`` (called again
        at the end: the start, to measure the change from; no second copy
        is held meanwhile). With ``against`` (the other side's
        ``grad_abs``) the first gradient, after the clip, is reduced
        against it leaf by leaf: ``grad_norms`` and ``grad_diff_norms``;
        without, its magnitudes come back as ``grad_abs``, float32 on the
        host."""
        params = self._put(make_params())
        batch = self._put(batch)
        # RMSProp's slot is zeros until the first update and waits on the
        # host between updates: at 706M parameters the gradient's program
        # (12.9 GB compiled for a v5e) has no room for it beside it.
        nu = None
        out = {"losses": [], "mtp_losses": []}
        with jax.default_matmul_precision("highest"):
            for i in range(steps):
                loss, mtp_loss, scale, g = self._grad(params, batch)
                if i == 0:
                    scale64 = float(scale)
                    leaves = jax.tree_util.tree_leaves(g)
                    if against is None:
                        out["grad_abs"] = [
                            np.abs(to_host(x, np.float64) * scale64)
                            .astype(np.float32) for x in leaves
                        ]
                    else:
                        out["grad_norms"], out["grad_diff_norms"] = [], []
                        for x, other in zip(leaves, against):
                            mine = np.abs(to_host(x, np.float64) * scale64)
                            out["grad_norms"].append(leaf_norm(mine))
                            out["grad_diff_norms"].append(
                                leaf_norm(other.astype(np.float64) - mine)
                            )
                on_device = (
                    jax.tree_util.tree_map(jnp.zeros_like, params)
                    if nu is None else self._put(nu)
                )
                params, on_device = self._update(params, on_device, g, scale)
                if i + 1 < steps:
                    nu = jax.device_get(on_device)
                del g, on_device
                out["losses"].append(float(loss))
                out["mtp_losses"].append(float(mtp_loss))
        del nu
        start = self._put(make_params())
        out["change_norms"] = [
            leaf_norm(to_host(a, np.float64) - to_host(b, np.float64))
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(start))
        ]
        return out


def leaf_gaps(program: dict, reference: dict):
    """Per leaf, the two numbers :func:`numbers` takes the worst of."""
    grad_norms = np.asarray(reference["grad_norms"])
    change_r = np.asarray(reference["change_norms"])
    return (
        np.asarray(reference["grad_diff_norms"]) / _scale(grad_norms),
        np.abs(np.asarray(program["change_norms"]) - change_r)
        / _scale(change_r),
    )


def numbers(program: dict, reference: dict) -> dict:
    """``compare.training_numbers``' four numbers from the two sides'
    reduced records (``reference`` followed with ``against`` the
    program's ``grad_abs``)."""
    steps = min(len(program["losses"]), len(reference["losses"]))
    p, r = program["losses"][:steps], reference["losses"][:steps]
    scale = max(max(abs(x) for x in r), 1e-30)
    grad_gaps, change_gaps = leaf_gaps(program, reference)
    return {
        "loss_gap_first": abs(p[0] - r[0]) / max(abs(r[0]), 1e-30),
        "loss_gap_later": max(
            (abs(a - b) / scale for a, b in zip(p[1:], r[1:])), default=0.0
        ),
        "grad_leaf_gap": float(np.max(grad_gaps)),
        "change_leaf_gap": float(np.max(change_gaps)),
    }
