from ray_tpu.parallel.mesh import (
    data_parallel_mesh, discover_devices, fsdp_mesh, make_mesh,
    mesh_axis_size,
)
from ray_tpu.parallel.sharding import (
    batch_sharding, batch_spec, context_parallel_attention,
    sharded_flash_attention,
    llama_param_shardings, llama_param_specs, replicated, shard_params,
)
from ray_tpu.parallel.train_step import (
    TrainState, build_eval_step, build_train_step, create_train_state,
    state_shardings,
)
from ray_tpu.parallel.zero import (
    ZeroTrainState, build_zero_train_step, constrain_opt_state,
    create_zero_state, zero_moment_shardings,
)

__all__ = [
    "make_mesh", "data_parallel_mesh", "discover_devices",
    "fsdp_mesh", "mesh_axis_size",
    "context_parallel_attention", "sharded_flash_attention",
    "llama_param_specs", "llama_param_shardings", "batch_spec",
    "batch_sharding", "shard_params", "replicated", "TrainState",
    "create_train_state", "build_train_step", "build_eval_step",
    "state_shardings",
    "ZeroTrainState", "build_zero_train_step", "create_zero_state",
    "zero_moment_shardings", "constrain_opt_state",
]
