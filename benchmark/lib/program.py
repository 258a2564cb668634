"""How the harness gets the system under test out of a configuration file:
the program's own factories by dotted path, with the file's arguments. This
is the only module of ``lib`` that touches the program."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import seeded


def resolve(dotted: str):
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def build_model(config: dict):
    kwargs = dict(config["model"]["kwargs"])
    if "compute_dtype" in kwargs:
        kwargs["compute_dtype"] = jnp.dtype(kwargs["compute_dtype"])
    if "channels" in kwargs:
        kwargs["channels"] = tuple(kwargs["channels"])
    return resolve(config["model"]["factory"])(**kwargs)


def param_shapes(net, config: dict):
    """The shapes of the program's parameter tree; no value is taken."""
    obs = jax.eval_shape(
        lambda: seeded.observation(
            jax.random.PRNGKey(0), (1, 1), config["observation"]
        )
    )
    return jax.eval_shape(
        net.init, jax.random.PRNGKey(0), obs,
        jax.ShapeDtypeStruct((1, 1), jnp.bool_), net.initial_state(1),
    )


def build_optimizer(config: dict):
    """clip_by_global_norm then RMSProp, as ``experiment.train`` chains
    them."""
    import optax

    opt = config["optimizer"]
    return optax.chain(
        optax.clip_by_global_norm(opt["grad_clip"]),
        optax.rmsprop(opt["learning_rate"], decay=opt["decay"],
                      eps=opt["eps"]),
    )


def loss_config(config: dict):
    return resolve("moolib_tpu.learner.ImpalaConfig")(**config["loss"])


def second_moments(opt_state):
    """The ``nu`` tree of the RMSProp state inside an optax chain state."""
    found = [
        node.nu for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "nu")
        ) if hasattr(node, "nu")
    ]
    if len(found) != 1:
        raise ValueError(f"expected one second-moment tree, found {len(found)}")
    return found[0]


def first_steps(step, state, batch, steps: int, decay: float):
    """The program's first ``steps`` steps through ``step(state, batch) ->
    (state, metrics)``, the call the window uses. Returns the state to go on
    from and what :func:`benchmark.lib.compare.training_numbers` compares:
    each step's loss, the magnitude of every element of the first gradient
    as RMSProp got it (out of its state after one step: nu = (1 - decay)
    g^2), and the parameters' change, leaf by leaf."""
    start = [np.asarray(x, np.float64)
             for x in jax.tree_util.tree_leaves(state.params)]
    losses, grad_abs = [], None
    for i in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["total_loss"]))
        if i == 0:
            grad_abs = [
                np.sqrt(np.asarray(x, np.float64) / (1.0 - decay))
                for x in jax.tree_util.tree_leaves(
                    second_moments(state.opt_state)
                )
            ]
    change = [
        np.asarray(a, np.float64) - b
        for a, b in zip(jax.tree_util.tree_leaves(state.params), start)
    ]
    return state, {"losses": losses, "grad_abs": grad_abs, "change": change}
