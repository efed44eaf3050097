"""apex_tpu.parallel — data parallelism over TPU meshes.

Reference surface (``apex/parallel/__init__.py``): ``DistributedDataParallel``,
``Reducer``, ``SyncBatchNorm``, ``convert_syncbn_model``,
``create_syncbn_process_group``, ``ReduceOp``, ``LARC``.
"""

from apex_tpu.optimizers.larc import LARC, larc
from apex_tpu.parallel import mesh, multiproc
from apex_tpu.parallel.moe import (
    gated_ffn,
    grouped_matmul,
    load_balance_loss,
    moe_apply,
    route,
)
from apex_tpu.parallel.pipeline import (
    pipeline_apply,
    stack_stage_params,
)
from apex_tpu.parallel.distributed import (
    DistributedDataParallel,
    ReduceConfig,
    ReduceOp,
    Reducer,
    all_gather,
    all_reduce,
    broadcast,
    pvary_params,
    reduce_gradients,
)
from apex_tpu.parallel.groups import (
    convert_syncbn_model,
    create_syncbn_process_group,
)
from apex_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    data_parallel_mesh,
    intended_specs,
    make_mesh,
    partition_spec_of,
    replicated_sharding,
    world_size,
)
from apex_tpu.parallel.sync_batchnorm import (
    BatchNorm,
    SyncBatchNorm,
    batchnorm_backward,
    batchnorm_backward_c_last,
    batchnorm_forward,
    batchnorm_forward_c_last,
    reduce_bn,
    reduce_bn_c_last,
    welford_mean_var,
    welford_mean_var_c_last,
    welford_parallel,
)

__all__ = [
    "DistributedDataParallel", "Reducer", "ReduceConfig", "ReduceOp",
    "all_reduce", "all_gather", "broadcast", "reduce_gradients",
    "pvary_params",
    "pipeline_apply", "stack_stage_params",
    "moe_apply", "route", "load_balance_loss", "grouped_matmul", "gated_ffn",
    "SyncBatchNorm", "BatchNorm", "convert_syncbn_model",
    "create_syncbn_process_group",
    "welford_mean_var", "welford_parallel", "batchnorm_forward",
    "reduce_bn", "batchnorm_backward", "welford_mean_var_c_last",
    "batchnorm_forward_c_last", "reduce_bn_c_last",
    "batchnorm_backward_c_last",
    "LARC", "larc",
    "mesh", "multiproc", "make_mesh", "data_parallel_mesh", "batch_sharding",
    "replicated_sharding", "world_size", "DATA_AXIS",
    "intended_specs", "partition_spec_of",
]
