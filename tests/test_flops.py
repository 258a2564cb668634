"""Analytic FLOPs accounting vs XLA's own cost model (CPU backend)."""

import jax
import jax.numpy as jnp
import pytest

from moolib_tpu.models import ImpalaNet
from moolib_tpu.utils.flops import (
    conv2d_flops,
    dense_flops,
    device_peak_flops,
    impala_forward_flops,
    impala_train_flops,
)


def test_flops_primitives():
    assert dense_flops(10, 20) == 400
    # 1x1 conv == dense per pixel
    assert conv2d_flops(5, 5, 1, 1, 8, 16) == 25 * dense_flops(8, 16)
    assert impala_train_flops(10) == 3 * 10 * impala_forward_flops()


def test_device_peak_lookup():
    assert device_peak_flops("TPU v5 lite") == pytest.approx(197e12)
    assert device_peak_flops("TPU v4") == pytest.approx(275e12)
    with pytest.raises(ValueError, match="no such chip"):
        device_peak_flops("no such chip")  # unknown is an error, not None


def test_impala_forward_flops_matches_xla():
    """The analytic count must agree with XLA's cost analysis within 10%
    (XLA additionally counts elementwise ops; convs dominate)."""
    net = ImpalaNet(num_actions=6)
    obs = jnp.zeros((1, 1, 84, 84, 4), jnp.uint8)
    done = jnp.zeros((1, 1), bool)
    params = net.init(jax.random.PRNGKey(0), obs, done, ())
    fn = jax.jit(lambda p, o, d: net.apply(p, o, d, ()))
    cost = fn.lower(params, obs, done).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost["flops"])
    mine = impala_forward_flops(num_actions=6)
    assert mine * 0.9 <= xla_flops <= mine * 1.1, (mine, xla_flops)
