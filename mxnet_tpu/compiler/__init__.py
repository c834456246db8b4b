"""mx.compiler — whole-graph symbolic compiler + persistent AOT cache.

Two coupled layers (ROADMAP item #2, ISSUE 11):

* `lower` / `passes`: a bound Symbol graph lowers through a graph-level
  pass pipeline (constant folding, CSE, dead-node elimination — the
  Relay/TVM playbook from PAPERS.md) into ONE `lower().compile()`d XLA
  program for the whole forward (and forward+backward), which
  `symbol/executor.py` dispatches instead of its op-by-op loop. Gated by
  `MXNET_TPU_WHOLE_GRAPH` (default on) with a counted, never-erroring
  fallback to op-by-op dispatch (`compiler.fallback.<reason>`).
* `cache`: compiled executables serialize to the `MXNET_TPU_AOT_CACHE`
  directory keyed by graph hash + shapes/dtypes + mesh + jax/library
  versions, with atomic writes, corruption-tolerant loads and keep=N
  eviction — `mx.serve`'s warmup executables and the train-step programs
  ride the same cache, so a fleet replica or a preempted elastic worker
  restores instead of recompiling (not measured on the chip: ROADMAP S4).
"""
from . import cache, lower, passes
from .cache import AOTCache, aot_cache, cache_key
from .lower import GraphProgram, UnsupportedGraphError
from .passes import (GraphIR, eliminate_common_subexpr, eliminate_dead_nodes,
                     fold_constants, from_symbol, graph_hash, run_pipeline)

__all__ = [
    "cache", "lower", "passes",
    "AOTCache", "aot_cache", "cache_key",
    "GraphProgram", "UnsupportedGraphError",
    "GraphIR", "from_symbol", "fold_constants", "eliminate_common_subexpr",
    "eliminate_dead_nodes", "run_pipeline", "graph_hash",
]
