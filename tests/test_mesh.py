"""Mesh/psum gradient path on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from moolib_tpu.parallel import (
    data_parallel_spec,
    dp_average_grads,
    make_mesh,
    pmean_gradients,
    psum_gradients,
    shard_batch,
)
from jax.sharding import NamedSharding, PartitionSpec as P


def test_make_mesh_shapes():
    mesh = make_mesh()
    assert mesh.devices.shape == (8, 1, 1, 1, 1)
    mesh2 = make_mesh(tp=2, sp=2)
    assert mesh2.devices.shape == (2, 2, 2, 1, 1)
    mesh3 = make_mesh(pp=2, ep=2)
    assert mesh3.devices.shape == (2, 1, 1, 2, 2)
    with pytest.raises(ValueError):
        make_mesh(dp=3, tp=3)


def test_shard_batch_places_on_dp():
    mesh = make_mesh()
    batch = {"obs": np.zeros((4, 16, 3), np.float32), "r": np.zeros((4, 16))}
    sharded = shard_batch(mesh, batch)
    # (trailing Nones in PartitionSpec are not normalized for equality)
    assert sharded["obs"].sharding.spec[1] == "dp"
    assert data_parallel_spec()[1] == "dp"
    # 16 rows over 8 dp shards -> 2 rows per device
    shard = sharded["obs"].addressable_shards[0]
    assert shard.data.shape == (4, 2, 3)


def test_psum_gradients_in_shard_map():
    mesh = make_mesh()

    def per_device(grads):
        return psum_gradients(grads)

    f = jax.jit(
        jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=P("dp"),
            out_specs=P("dp"),
        )
    )
    g = jnp.arange(8.0)  # one value per device
    out = f(g)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0 * 7 / 2))


def test_data_parallel_train_step_grads_match_single_device():
    """dp-sharded grad step == single-device grad on the full batch."""
    from moolib_tpu.models import A2CNet

    mesh = make_mesh()
    net = A2CNet(num_actions=3, hidden_sizes=(16,))
    T, B, F = 4, 16, 5
    rng = np.random.default_rng(0)
    obs = rng.standard_normal((T, B, F)).astype(np.float32)
    done = np.zeros((T, B), bool)
    params = net.init(jax.random.key(0), jnp.asarray(obs[:, :1]),
                      jnp.asarray(done[:, :1]), ())

    def loss_fn(p, o, d):
        (logits, baseline), _ = net.apply(p, o, d, ())
        return jnp.mean(logits**2) + jnp.mean(baseline**2)

    # Single-device reference.
    ref_grads = jax.grad(loss_fn)(params, jnp.asarray(obs), jnp.asarray(done))

    # dp-sharded: jax.grad w.r.t. replicated params auto-psums across dp
    # (JAX >=0.9 semantics); divide by axis size for the global mean.
    def step(p, o, d):
        g = jax.grad(loss_fn)(p, o, d)
        return dp_average_grads(g)

    sharded_step = jax.jit(
        jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(None, "dp"), P(None, "dp")),
            out_specs=P(),
        )
    )
    dp_grads = sharded_step(params, jnp.asarray(obs), jnp.asarray(done))
    for a, b in zip(jax.tree_util.tree_leaves(ref_grads),
                    jax.tree_util.tree_leaves(dp_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
