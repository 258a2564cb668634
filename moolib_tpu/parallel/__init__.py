from .accumulator import Accumulator
from .stats import GlobalStatsAccumulator
from .mesh import (
    data_parallel_spec,
    dp_average_grads,
    make_mesh,
    pmean_gradients,
    psum_gradients,
    replicated_spec,
    shard_batch,
)
from .pipeline import (
    MICRO_SPEC,
    pipeline_apply,
    shard_microbatches,
    stack_stage_params,
    unshard_microbatches,
)
from .tp import (
    count_sharded_leaves,
    impala_tp_specs,
    shard_params,
    sharded_init_opt_state,
    transformer_tp_specs,
)

__all__ = [
    "Accumulator",
    "GlobalStatsAccumulator",
    "make_mesh",
    "data_parallel_spec",
    "replicated_spec",
    "psum_gradients",
    "pmean_gradients",
    "dp_average_grads",
    "shard_batch",
    "count_sharded_leaves",
    "impala_tp_specs",
    "shard_params",
    "sharded_init_opt_state",
    "transformer_tp_specs",
    "MICRO_SPEC",
    "pipeline_apply",
    "shard_microbatches",
    "stack_stage_params",
    "unshard_microbatches",
]
