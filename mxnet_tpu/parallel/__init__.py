"""mxnet_tpu.parallel — TPU-native parallelism subsystem.

The reference scales via KVStore backends (src/kvstore/: CommDevice NVLink
reduce, KVStoreNCCL ring allreduce, ps-lite parameter server over ZMQ) plus a
manual `group2ctx` model-parallel primitive (src/executor/graph_executor.cc).
The TPU-native answer is one unified mechanism: a `jax.sharding.Mesh` over the
chip topology, `NamedSharding`/`PartitionSpec` annotations on parameters and
activations, and XLA-inserted collectives riding ICI (intra-slice) / DCN
(cross-slice). This package holds that machinery:

* mesh.py         — mesh construction/current-mesh scoping (`MeshConfig`)
* sharding.py     — Megatron/FSDP-style per-parameter PartitionSpec rules
* collectives.py  — psum/all_gather/ppermute/reduce_scatter wrappers + comm bench
* dist.py         — multi-controller init (jax.distributed) with DMLC_* env compat
* flash_attention.py — fused attention kernels (Pallas on TPU, lax fallback),
                    on (B, H, S, D) or on a projection's own (B, S, H, D)
* ring_attention.py  — sequence-parallel ring attention over a mesh axis
* train_step.py   — compile a whole train step (fwd+bwd+opt) under shardings
"""
from .mesh import (MeshConfig, create_mesh, current_mesh, local_mesh,
                   mesh_scope, auto_mesh)
from .sharding import (ShardingRules, LLAMA_RULES, BERT_RULES,
                       named_sharding, shard_pytree, replicate_pytree,
                       logical_to_spec)
from .collectives import (all_reduce, all_gather, reduce_scatter, ppermute,
                          barrier, allreduce_bench)
from .dist import initialize, is_initialized, rank, num_workers
from .flash_attention import (flash_attention, flash_attention_bshd,
                              flash_attention_packed, pack_qkv)
from .ring_attention import ring_attention
from .train_step import ShardedTrainStep
from .checkpoint import (save_sharded, restore_sharded, latest_step,
                         save_train_state, restore_train_state)

__all__ = [
    "MeshConfig", "create_mesh", "current_mesh", "local_mesh", "mesh_scope",
    "auto_mesh", "ShardingRules", "LLAMA_RULES", "BERT_RULES",
    "named_sharding", "shard_pytree", "replicate_pytree", "logical_to_spec",
    "all_reduce", "all_gather", "reduce_scatter", "ppermute", "barrier",
    "allreduce_bench", "initialize", "is_initialized", "rank", "num_workers",
    "flash_attention", "flash_attention_bshd", "flash_attention_packed",
    "pack_qkv", "ring_attention",
    "ShardedTrainStep",
]
