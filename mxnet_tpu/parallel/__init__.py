"""mxnet_tpu.parallel — mesh parallelism (DP/FSDP/TP/PP/SP/EP).

The reference's distribution stack (SURVEY.md §2.3: KVStore + ps-lite + NCCL
+ device groups) re-imagined as named mesh axes + XLA collectives.  The public
pieces:

- make_mesh / MeshScope      device mesh with canonical axis names
- ShardingRules + presets    name-pattern → PartitionSpec parameter placement
- TrainStep / EvalStep       one-XLA-program fused sharded train/eval step
- functional_call            pure-function view of any Gluon block
"""
from .mesh import (AXES, MeshScope, current_mesh, default_mesh, make_mesh,
                   named_sharding, replicated, shard_map, validate_specs)
from .sharding import (ShardingRules, batch_spec, causal_lm_tp_rules,
                       fsdp_rules, param_sharding, tp_dense_rules)
from .functional import functional_call, param_names_and_values
from .moe import (DroplessMoEFFN, MoEFFN, moe_dispatch, publish_load,
                  route_topk)
from .pipeline import PipelineStack, gpipe
from .sequence import ring_attention, sp_attention, ulysses_attention
from .prefetch import DevicePrefetcher
from .step import (EvalStep, TrainStep, add_transfer_hook,
                   remove_transfer_hook)
from .quantize import (ACTIVATION_REDUCE_MODES, GRAD_REDUCE_MODES,
                       all_reduce_activations, cast_bf16,
                       dequantize_chunked, quantize_chunked,
                       reduce_gradients)
from .checkpoint import (CheckpointManager, CheckpointMismatchError,
                         list_checkpoints, load_snapshot_params,
                         load_train_step, load_train_step_sharded,
                         resume_latest,
                         save_train_step, save_train_step_sharded,
                         wait_for_new)

__all__ = [
    "load_train_step", "save_train_step",
    "load_train_step_sharded", "save_train_step_sharded",
    "CheckpointManager", "CheckpointMismatchError", "list_checkpoints",
    "resume_latest", "wait_for_new", "load_snapshot_params",
    "AXES", "MeshScope", "current_mesh", "default_mesh", "make_mesh",
    "named_sharding", "replicated",
    "ShardingRules", "batch_spec", "fsdp_rules", "param_sharding",
    "tp_dense_rules", "causal_lm_tp_rules",
    "functional_call", "param_names_and_values",
    "ring_attention", "sp_attention", "ulysses_attention",
    "PipelineStack", "gpipe",
    "MoEFFN", "moe_dispatch", "DroplessMoEFFN", "route_topk", "publish_load",
    "EvalStep", "TrainStep", "DevicePrefetcher",
    "add_transfer_hook", "remove_transfer_hook",
    "GRAD_REDUCE_MODES", "quantize_chunked", "dequantize_chunked",
    "cast_bf16", "reduce_gradients",
    "ACTIVATION_REDUCE_MODES", "all_reduce_activations",
]
