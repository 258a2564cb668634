"""Milliseconds of the loop's thread per update spent getting the learn
batch ready for the device: ``learn_batch_get`` (the Batcher's
concatenate on the host), ``learn_stage`` (its copy to the device and the
sharding) and ``grad_stage`` (the reduced gradients back to the device)."""
from benchmark.lib.spans import ms_per_update


def read(readings, context):
    return ms_per_update(
        readings, ("learn_batch_get", "learn_stage", "grad_stage")
    )
