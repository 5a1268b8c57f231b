"""Multi-chip parallelism: meshes, shardings, and collective train steps."""

from bpe_transformer_tpu.parallel.mesh import (
    batch_sharding,
    initialize_distributed,
    make_hybrid_mesh,
    make_mesh,
    replicated,
)
from bpe_transformer_tpu.parallel.sharding import (
    param_shardings,
    param_specs,
    shard_params,
    zero1_opt_shardings,
    zero1_opt_specs,
)
from bpe_transformer_tpu.parallel.pp import (
    init_pp_opt_state,
    make_pp_train_step,
    shard_pp_params,
    stack_pipeline_params,
    unstack_pipeline_params,
)
from bpe_transformer_tpu.parallel.ring_attention import (
    make_ring_attention,
    ring_self_attention,
)
from bpe_transformer_tpu.parallel.ulysses import ulysses_attention
from bpe_transformer_tpu.parallel.sp import (
    make_sp_train_step,
    shard_sp_batch,
    sp_forward,
)
from bpe_transformer_tpu.parallel.train_step import (
    make_dp_train_step,
    make_gspmd_train_step,
    partitioned_config,
    shard_batch,
)

__all__ = [
    "batch_sharding",
    "init_pp_opt_state",
    "make_pp_train_step",
    "shard_pp_params",
    "stack_pipeline_params",
    "unstack_pipeline_params",
    "make_ring_attention",
    "make_sp_train_step",
    "ring_self_attention",
    "shard_sp_batch",
    "sp_forward",
    "ulysses_attention",
    "initialize_distributed",
    "make_dp_train_step",
    "make_gspmd_train_step",
    "make_hybrid_mesh",
    "make_mesh",
    "param_shardings",
    "param_specs",
    "partitioned_config",
    "replicated",
    "shard_batch",
    "shard_params",
    "zero1_opt_shardings",
    "zero1_opt_specs",
]
