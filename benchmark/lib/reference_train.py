"""The plain side of every training comparison: V-trace targets, the IMPALA
loss, its gradient, and the optimizer, in float32 ``jax.numpy`` written from
the equations. Imports nothing of the program.

A configuration's ``reference/<name>.py`` supplies only ``forward``; the
rest is shared here because both configurations train under the same loss.

Precision. ``cast`` is applied to both operands of every convolution and
matrix product of the forward pass. The reference proper uses the identity
under ``jax.default_matmul_precision("highest")``. The *control* of a
bfloat16 configuration uses :func:`fp8_cast`: the step that would tempt a
later PR (fp8 e4m3 operands with a per-tensor scale, gradients passed
straight through), which ``correct`` has to refuse.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np


def identity_cast(x):
    return x


def fp8_cast(x):
    """Round ``x`` to float8 e4m3 under a per-tensor scale that puts its
    largest magnitude at the format's largest (448); straight-through
    gradient, as fp8 training recipes do."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def bf16_cast(x):
    """Operands rounded to bfloat16: the precision the configurations
    state. Used only to show that the comparison passes at it."""
    x = x.astype(jnp.float32)
    q = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


CASTS = {"float32": identity_cast, "bfloat16": bf16_cast, "fp8": fp8_cast}
# The nearest precision below the one a configuration states.
CONTROL_OF = {"float32": "bfloat16", "bfloat16": "fp8"}


def _take(logp, actions):
    return jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]


def vtrace_targets(log_rhos, discounts, rewards, values, bootstrap,
                   rho_bar=1.0, c_bar=1.0, pg_rho_bar=1.0, lambda_=1.0):
    """Espeholt et al. 2018, eq. 1 and section 4.2, time-major [T, b]:

        delta_t = rho_t (r_t + g_t V_{t+1} - V_t)
        v_t - V_t = delta_t + g_t c_t (v_{t+1} - V_{t+1})
        adv_t = rho'_t (r_t + g_t v_{t+1} - V_t)
    """
    rhos = jnp.exp(log_rhos)
    rho = jnp.minimum(rho_bar, rhos)
    c = lambda_ * jnp.minimum(c_bar, rhos)
    v_next = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    deltas = rho * (rewards + discounts * v_next - values)

    def back(acc, x):
        delta, g, ct = x
        acc = delta + g * ct * acc
        return acc, acc

    _, diffs = jax.lax.scan(
        back, jnp.zeros_like(bootstrap), (deltas, discounts, c), reverse=True
    )
    vs = values + diffs
    vs_next = jnp.concatenate([vs[1:], bootstrap[None]], axis=0)
    adv = jnp.minimum(pg_rho_bar, rhos) * (
        rewards + discounts * vs_next - values
    )
    return vs, adv


def chunk_loss(params, chunk, forward, loss, denom, cast):
    """The IMPALA loss of a block of columns, as its share of the whole
    batch's loss: every term is a mean over T x B, so sums over the block
    divided by the whole batch's ``denom`` add up over blocks."""
    logits, baseline, _ = forward(
        params, chunk["obs"], chunk["done"], chunk["core_state"], cast
    )
    target_logits, values, bootstrap = logits[:-1], baseline[:-1], baseline[-1]
    rewards = chunk["rewards"][1:]
    if loss["reward_clip"] > 0:
        rewards = jnp.clip(rewards, -loss["reward_clip"], loss["reward_clip"])
    discounts = (
        1.0 - chunk["done"][1:].astype(jnp.float32)
    ) * loss["discounting"]
    logp = jax.nn.log_softmax(target_logits, axis=-1)
    target_lp = _take(logp, chunk["actions"])
    behavior_lp = _take(
        jax.nn.log_softmax(chunk["behavior_logits"], axis=-1),
        chunk["actions"],
    )
    # The targets are constants of the optimisation.
    vs, adv = jax.lax.stop_gradient(
        vtrace_targets(
            target_lp - behavior_lp, discounts, rewards, values, bootstrap
        )
    )
    pg = -jnp.sum(target_lp * adv)
    value = 0.5 * jnp.sum((vs - values) ** 2)
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    return (
        pg + loss["baseline_cost"] * value - loss["entropy_cost"] * entropy
    ) / denom


def _columns(batch, lo, size: int):
    """``size`` columns from ``lo`` on of a learn batch: axis 1, axis 0 for
    the state. ``lo`` may be traced, so one program cuts every block."""
    def cut(axis):
        return lambda x: jax.lax.dynamic_slice_in_dim(x, lo, size, axis)

    out = {
        k: jax.tree_util.tree_map(cut(1), v)
        for k, v in batch.items() if k != "core_state"
    }
    out["core_state"] = tuple(cut(0)(x) for x in batch["core_state"])
    return out


class Follower:
    """Follows the program's first steps on the same batch from the same
    weights: loss and gradient in blocks of columns (the networks and
    V-trace are independent across columns), then
    ``clip_by_global_norm`` and RMSProp as optax defines them,

        nu <- decay nu + (1 - decay) g^2,  p <- p - lr g / sqrt(nu + eps),

    with nu starting at 0.
    """

    def __init__(self, forward, config, precision="float32",
                 columns_per_chunk=64, device=None):
        self.opt = config["optimizer"]
        self.columns = columns_per_chunk
        self.device = device  # one chip holds the reference, whatever the cell's mesh
        cast = CASTS[precision]
        loss = dict(config["loss"])

        def grad(params, chunk, denom):
            return jax.value_and_grad(chunk_loss)(
                params, chunk, forward, loss, denom, cast
            )

        self._grad = jax.jit(grad)
        self._take = jax.jit(_columns, static_argnums=2)
        self._forward = jax.jit(
            lambda p, o, d, s: forward(p, o, d, s, cast)
        )

    def _put(self, tree):
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def forward(self, params, obs, done, core_state):
        with jax.default_matmul_precision("highest"):
            return self._forward(
                *self._put((params, obs, done, core_state))
            )

    def gradient(self, params, batch):
        T1, B = batch["done"].shape
        size = min(self.columns, B)
        if B % size:
            raise ValueError(f"{B} columns do not split into blocks of {size}")
        denom = float((T1 - 1) * B)
        total, grads = 0.0, None
        with jax.default_matmul_precision("highest"):
            for lo in range(0, B, size):
                value, g = self._grad(
                    params, self._put(self._take(batch, lo, size)), denom
                )
                total = total + value
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g
                )
        return total, grads

    def follow(self, params, batch, steps):
        """``steps`` optimizer steps. Returns the loss of each, the
        magnitude of every element of the first gradient as RMSProp gets
        it (after the clip), and the parameters' change, leaf by leaf."""
        opt = self.opt
        params = self._put(jax.tree_util.tree_map(
            lambda x: jnp.asarray(x, jnp.float32), params
        ))
        start = params
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first = [], None
        for _ in range(steps):
            loss, g = self.gradient(params, batch)
            norm = jnp.sqrt(sum(
                jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)
            ))
            scale = jnp.where(
                norm < opt["grad_clip"], 1.0, opt["grad_clip"] / norm
            )
            g = jax.tree_util.tree_map(lambda x: x * scale, g)
            if first is None:
                first = [np.abs(np.asarray(x, np.float64))
                         for x in jax.tree_util.tree_leaves(g)]
            nu = jax.tree_util.tree_map(
                lambda n, x: opt["decay"] * n + (1 - opt["decay"]) * x * x,
                nu, g,
            )
            params = jax.tree_util.tree_map(
                lambda p, x, n: p
                - opt["learning_rate"] * x / jnp.sqrt(n + opt["eps"]),
                params, g, nu,
            )
            losses.append(float(loss))
        change = [
            np.asarray(a, np.float64) - np.asarray(b, np.float64)
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(start))
        ]
        return {"losses": losses, "grad_abs": first, "change": change}


class Followers:
    """A configuration's followers by precision, each built (and its
    programs compiled) on first use: ``followers("float32")`` is the
    reference, ``followers(CONTROL_OF[...])`` the control."""

    def __init__(self, config, columns_per_chunk, device=None):
        self.config, self.columns, self.device = (
            config, columns_per_chunk, device
        )
        self.forward = importlib.import_module(
            f"benchmark.reference.{config['reference']}"
        ).forward
        self.built = {}

    def __call__(self, precision: str) -> Follower:
        if precision not in self.built:
            self.built[precision] = Follower(
                self.forward, self.config, precision, self.columns,
                self.device,
            )
        return self.built[precision]
