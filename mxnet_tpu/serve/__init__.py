"""`mx.serve` — fault-tolerant continuous-batching inference.

Training got the first seven PRs; this subsystem spends that
infrastructure on the north star's other half: serving. One replica is an
`InferenceServer` — a continuous-batching scheduler (requests join/leave
the running batch between decode steps) over a **refcounted paged
KV-cache allocator** (`KVBlockPool`: fixed-size blocks + free-list +
hash-consed shared-prefix index, sized by ``MXNET_TPU_SERVE_KV_BLOCKS``
× ``MXNET_TPU_SERVE_KV_BLOCK``) and **fixed-shape AOT programs**
(`ServePrograms`: ONE multi-stream chunk-prefill window, ONE decode
executable, a CoW block copy, and the draft/verify pair when speculative
decoding is configured — every signature compiled at warm-up, so
admission never retraces mid-traffic). `ReplicaGroup` supervises N
replicas over one shared queue.

Serving v2 throughput layers, all attributable in telemetry: burst arrivals prefill TOGETHER in chunk windows
interleaved with decode (``serve.prefill_chunks``); N users of one
system prompt share its KV blocks by refcount with copy-on-write at the
divergence block (``serve.prefix.*``); a small draft model multiplies
greedy tokens/s where decode is HBM-bound (``serve.spec.*``,
byte-identical output by construction); and temperature/top-k/top-p
sampling draws are keyed on (stream seed, position) so kill-recovery
replays them exactly.

The robustness contract, end to end:

* structured `Overloaded` load-shedding when the queue or KV pool is
  exhausted — never an OOM;
* per-request deadlines (`DeadlineExceeded` carries partial output) and
  retry budgets (``MXNET_TPU_RETRIES``);
* ``serve.admit`` / ``serve.step`` fault sites under
  ``MXNET_TPU_FAULT_PLAN``, the hang watchdog around the decode loop;
* kill-a-replica-mid-stream recovery: the replica drains, its in-flight
  streams re-enter the queue and resume via re-prefill from their
  already-emitted tokens — byte-identical output, no token lost or
  duplicated;
* telemetry throughout: tokens/s, TTFT/TPOT histograms, queue depth and
  KV occupancy gauges, flight-recorder ``step_event`` records (with the
  active/completed request ids per step), and a `RequestTrace` per
  request — queue-wait / prefill / per-token decode / recovery spans
  tiling its wall-clock, queryable via the exporter's ``/requests``
  endpoint (`mx.telemetry.request_traces()`), embedded in
  ``DeadlineExceeded.request_trace``, one chrome-trace row per request.

Quickstart::

    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import CONFIGS, llama_init
    import jax

    cfg = CONFIGS["llama_110m"]
    params = llama_init(jax.random.PRNGKey(0), cfg)
    server = mx.serve.InferenceServer(params, cfg).warmup()
    h = server.submit(mx.serve.Request([1, 2, 3], max_new_tokens=32))
    server.run()              # or ReplicaGroup(...).start() for a fleet
    print(h.result())
"""
from __future__ import annotations

from .errors import DeadlineExceeded, Overloaded, ServeError
from .kv_cache import KVBlockPool
from .programs import (ServePrograms, default_chunk_size,
                       default_prefill_rows, default_spec_k)
from .replica import ReplicaGroup
from .sampling import sample_tokens
from .scheduler import (InferenceServer, Request, RequestQueue,
                        StreamHandle)

__all__ = ["ServeError", "Overloaded", "DeadlineExceeded", "KVBlockPool",
           "ServePrograms", "default_chunk_size", "default_prefill_rows",
           "default_spec_k", "sample_tokens", "InferenceServer",
           "Request", "RequestQueue", "StreamHandle", "ReplicaGroup"]
