"""Device-mesh utilities: the intra-cohort (ICI) data plane.

This is the TPU-native replacement for the reference's dense gradient path
(reference: the pinned-CPU gradient bundles + software tree allreduce of
src/accumulator.cc:880-1033 — on TPU those become XLA collectives over the
ICI mesh inside the jitted train step, per the design note in SURVEY.md §5).

Axis convention used across the framework:
  - ``dp``: data parallel (gradient psum rides here)
  - ``tp``: tensor/model parallel (Megatron-sharded params, parallel/tp.py)
  - ``sp``: sequence/context parallel (ring/zigzag attention)
  - ``pp``: pipeline parallel (GPipe microbatching, parallel/pipeline.py)
  - ``ep``: expert parallel; no user until the exchange of tokens between
    chips is written for ``moe_dropless`` (ROADMAP R19)
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "data_parallel_spec",
    "replicated_spec",
    "psum_gradients",
    "pmean_gradients",
    "dp_average_grads",
    "shard_batch",
    "batch_leaf_spec",
    "batch_specs",
    "pvary_if_needed",
]


def pvary_if_needed(x, axis_name: str):
    """Mark a value device-varying over ``axis_name`` for shard_map's vma
    typing (no-op if already varying). Needed when a fresh constant enters
    a scan whose body makes it varying — the initial carry must match."""
    if axis_name in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, (axis_name,), to="varying")


def make_mesh(
    dp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dp, tp, sp, pp, ep) mesh over the available devices.

    ``dp`` defaults to "whatever is left": n_devices // (tp * sp * pp * ep).
    Size-1 axes cost nothing — specs that never name them are unaffected.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    rest = tp * sp * pp * ep
    if dp is None:
        if n % rest != 0:
            raise ValueError(
                f"{n} devices not divisible by tp*sp*pp*ep={rest}"
            )
        dp = n // rest
    if dp * rest != n:
        raise ValueError(
            f"mesh {dp}x{tp}x{sp}x{pp}x{ep} needs {dp * rest} devices, "
            f"have {n}"
        )
    arr = np.asarray(devices).reshape(dp, tp, sp, pp, ep)
    return Mesh(arr, axis_names=("dp", "tp", "sp", "pp", "ep"))


def data_parallel_spec() -> P:
    """Batch-dim sharding over dp (time-major [T, B, ...]: shard axis 1)."""
    return P(None, "dp")


def replicated_spec() -> P:
    return P()


def batch_leaf_spec(x, batch_axis: int = 1, axis_name: str = "dp") -> P:
    """PartitionSpec sharding ``batch_axis`` of one leaf over ``axis_name``;
    leaves with too few dims (scalars, per-step vectors) replicate."""
    nd = np.ndim(x)
    if nd <= batch_axis:
        return P()
    spec = [None] * nd
    spec[batch_axis] = axis_name
    return P(*spec)


def batch_specs(batch: dict, batch_axes: Optional[dict] = None,
                axis_name: str = "dp", batch_axis: int = 1) -> dict:
    """Per-leaf PartitionSpecs for a learn-batch dict.

    ``batch_axes`` maps top-level keys to the axis carrying the batch dim;
    default is ``batch_axis`` (axis 1, time-major [T, B, ...]) for everything
    except ``core_state``, whose leaves are [B, ...] (axis 0).
    """
    axes = _resolve_batch_axes(batch_axes, batch_axis)
    return {
        k: jax.tree_util.tree_map(
            lambda x, a=axes.get(k, batch_axis): batch_leaf_spec(
                x, a, axis_name
            ),
            v,
        )
        for k, v in batch.items()
    }


def _resolve_batch_axes(batch_axes: Optional[dict], batch_axis: int) -> dict:
    """Single source of truth for per-key batch axes, shared by
    :func:`batch_specs` (jit in_specs) and :func:`shard_batch` (device_put)
    so placements always match the step's in_shardings."""
    axes = dict(batch_axes or {})
    axes.setdefault("core_state", 0)
    return axes


def shard_batch(mesh: Mesh, batch, batch_axis: int = 1,
                batch_axes: Optional[dict] = None):
    """Place a host batch onto the mesh, sharded over dp along its batch axis.

    For a top-level dict, per-key axes follow :func:`batch_specs` (so a
    ``core_state`` entry shards on axis 0 automatically); any other pytree
    shards every leaf on ``batch_axis``.
    """
    if isinstance(batch, dict):
        axes = _resolve_batch_axes(batch_axes, batch_axis)
        return {
            k: jax.tree_util.tree_map(
                lambda x, a=axes.get(k, batch_axis): jax.device_put(
                    x, NamedSharding(mesh, batch_leaf_spec(x, a))
                ),
                v,
            )
            for k, v in batch.items()
        }

    def _put(x):
        return jax.device_put(
            x, NamedSharding(mesh, batch_leaf_spec(x, batch_axis))
        )

    return jax.tree_util.tree_map(_put, batch)


def psum_gradients(grads, axis_name: str = "dp"):
    """Sum *varying* values over a mesh axis — call INSIDE shard_map/jit.

    NOTE (JAX >= 0.9 varying-axes semantics): ``jax.grad`` taken inside
    shard_map w.r.t. a REPLICATED (unvarying) parameter already psums the
    cotangent across the axis — the returned gradient is the global sum and
    identical on every device. Calling psum/pmean on it again is wrong/
    useless. Use :func:`dp_average_grads` for the canonical DP train step;
    reserve this for genuinely per-device (varying) values such as metrics.
    """
    return jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_name), grads
    )


def pmean_gradients(grads, axis_name: str = "dp"):
    """pmean of varying values (e.g. per-device losses/metrics)."""
    return jax.tree_util.tree_map(
        lambda g: jax.lax.pmean(g, axis_name), grads
    )


def dp_average_grads(grads, axis_name: str = "dp"):
    """Convert auto-summed grads of a per-device-MEAN loss into global-mean
    gradients: divide by the axis size.

    The canonical data-parallel step on the ICI mesh (the XLA-native
    replacement for the reference's gradient allreduce machinery,
    src/accumulator.cc:1005-1033)::

        def step(params, batch):           # inside shard_map
            loss, grads = jax.value_and_grad(local_mean_loss)(params, batch)
            grads = dp_average_grads(grads)        # global mean
            loss = jax.lax.pmean(loss, "dp")       # varying -> mean
            ...

    ``jax.grad`` w.r.t. replicated params inside shard_map yields
    sum_d grad(mean_loss_d) = n * grad(global_mean_loss); dividing by the
    axis size recovers the global-mean gradient exactly.
    """
    n = jax.lax.axis_size(axis_name)
    return jax.tree_util.tree_map(lambda g: g / n, grads)
