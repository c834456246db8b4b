#!/usr/bin/env python
"""Parse training logs — or telemetry JSON dumps — into a summary table.
reference: tools/parse_log.py — extracts train/val accuracy and epoch time
from the logging output of fit()/Speedometer (`Epoch[3] Batch [100] Speed:
... accuracy=0.9`, `Epoch[3] Validation-accuracy=0.91`, `Epoch[3] Time
cost=12.3`).

Telemetry mode (--telemetry, or auto-detected when the file is a JSON
object): flattens a `mx.telemetry.dump()` snapshot — or a
`mx.profiler.dump()` file embedding one under its "telemetry" key — into
the same markdown/csv table shape the log mode produces."""
from __future__ import annotations

import argparse
import json
import re
import sys


def parse(lines, metric="accuracy"):
    train_re = re.compile(
        r"Epoch\[(\d+)\].*?Train-" + metric + r"=([\d.eE+-]+)")
    batch_re = re.compile(
        r"Epoch\[(\d+)\].*?" + metric + r"=([\d.eE+-]+)")
    val_re = re.compile(
        r"Epoch\[(\d+)\].*?Validation-" + metric + r"=([\d.eE+-]+)")
    time_re = re.compile(r"Epoch\[(\d+)\].*?Time cost=([\d.eE+-]+)")
    rows = {}

    def row(e):
        return rows.setdefault(int(e), {"train": None, "val": None,
                                        "time": None})

    for line in lines:
        m = val_re.search(line)
        if m:
            row(m.group(1))["val"] = float(m.group(2))
            continue
        m = time_re.search(line)
        if m:
            row(m.group(1))["time"] = float(m.group(2))
            continue
        m = train_re.search(line) or batch_re.search(line)
        if m:
            row(m.group(1))["train"] = float(m.group(2))  # last batch wins
    return rows


def parse_telemetry(obj):
    """Flatten a telemetry snapshot into [(metric, kind, count, value, max)]
    rows. Accepts either a raw `telemetry.dump()` object or a
    `profiler.dump()` object with the snapshot under "telemetry"."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    rows = []
    for name, value in sorted(obj.get("counters", {}).items()):
        rows.append((name, "counter", "", value, ""))
    for name, g in sorted(obj.get("gauges", {}).items()):
        rows.append((name, "gauge", "", g.get("value"), g.get("max")))
    for name, h in sorted(obj.get("histograms", {}).items()):
        avg = h.get("avg")
        rows.append((name, "histogram", h.get("count"),
                     round(avg, 3) if avg is not None else "",
                     h.get("max")))
    return rows


def _print_telemetry(rows, fmt):
    if fmt == "markdown":
        print("| metric | kind | count | value | max |")
        print("| --- | --- | --- | --- | --- |")
        line = "| %s | %s | %s | %s | %s |"
    else:
        print("metric,kind,count,value,max")
        line = "%s,%s,%s,%s,%s"
    for r in rows:
        print(line % r)


# the headline resilience events, in narrative order; per-site counters
# (resilience.retries.kvstore.push, ...) list after their total. The v2
# events tell the elastic/commit/preempt story: shrink and grow-back,
# commit elections (+ rank_ahead = mid-commit-crash recoveries), and
# proactive (notice-triggered) checkpoints.
_RESILIENCE_EVENTS = ("faults_injected", "retries", "retry_exhausted",
                      "stalls", "restores", "checkpoints",
                      "proactive_checkpoints", "mesh_shrinks", "mesh_grows",
                      "commit.elections", "commit.rank_ahead",
                      "preempt.notices", "rollbacks", "skipped_batches")

# integrity-plane counters living OUTSIDE the resilience.* namespace — the
# divergence sentinel (integrity.*) and checksum-verified restores
# (checkpoint.corrupt*) are part of the same recovery narrative, so the
# --resilience table lists them explicitly rather than losing them to the
# unknown-prefix scan.
_INTEGRITY_PREFIXES = ("integrity.", "checkpoint.corrupt", "comm.checksum.")


def parse_resilience(obj):
    """Extract the resilience story from a telemetry snapshot: one row per
    `resilience.*` counter — was the run clean, noisy-but-recovered, or
    restart-heavy? Returns [(event, site, count)]."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    rows = []
    for event in _RESILIENCE_EVENTS:
        total_key = "resilience.%s" % event
        if total_key in counters:
            rows.append((event, "total", counters[total_key]))
        prefix = total_key + "."
        for name in sorted(counters):
            if name.startswith(prefix):
                rows.append((event, name[len(prefix):], counters[name]))
    # the commit-elected step rides a gauge (it is a frontier, not a count)
    elected = obj.get("gauges", {}).get("resilience.commit.elected_step")
    if elected is not None:
        rows.append(("commit.elected_step", "latest", elected.get("value")))
    # unknown resilience.* counters (future events) still surface
    known = {"resilience.%s" % e for e in _RESILIENCE_EVENTS}
    for name in sorted(counters):
        if name.startswith("resilience.") and name not in known and \
                not any(name.startswith("resilience.%s." % e)
                        for e in _RESILIENCE_EVENTS):
            rows.append((name[len("resilience."):], "total", counters[name]))
    # the integrity plane: sentinel trips (integrity.divergences.<site>),
    # AMP overflow skips, corrupt-checkpoint fallbacks, wire checksums
    for name in sorted(counters):
        if any(name.startswith(p) for p in _INTEGRITY_PREFIXES):
            rows.append((name, "total", counters[name]))
    return rows


def _print_resilience(rows, fmt):
    if not rows:
        # nothing on stdout: a header with zero rows reads as data to
        # downstream CSV consumers
        print("no resilience.* counters in this dump (clean run or "
              "telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| event | site | count |")
        print("| --- | --- | --- |")
        line = "| %s | %s | %s |"
    else:
        print("event,site,count")
        line = "%s,%s,%s"
    for r in rows:
        print(line % r)


def parse_comm(obj):
    """Extract the gradient-comm story from a telemetry snapshot: bucket
    counters (`comm.bucket.*`), launched collectives (`comm.collectives`),
    kvstore payload counters, and derived ratios — was the sync bucketed
    (few big launches) or per-param (many small ones)?
    Returns [(metric, value)] rows."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    rows = []
    ordered = ("comm.collectives", "comm.reduce_scatter", "comm.all_gather",
               "comm.bucket.count", "comm.bucket.bytes",
               "comm.bucket.skipped", "comm.ready.rounds",
               "comm.ready.flush_during_backward",
               "comm.ready.first_flush_before_backward_end",
               "comm.ready.aborted", "comm.zero.pipelined",
               "comm.autotune.sweeps", "kvstore.push_calls",
               "kvstore.push_bytes", "kvstore.pull_calls",
               "kvstore.pull_bytes")
    for name in ordered:
        if name in counters:
            rows.append((name, counters[name]))
    for name in sorted(counters):
        if name.startswith("comm.bucket.flush_reason."):
            rows.append((name, counters[name]))
    # ZeRO weight-update sharding: sharded-state footprint + fused-update
    # latency ride the same table (the --comm story is the whole sync)
    state_gauge = obj.get("gauges", {}).get("opt.state_bytes_per_rank")
    if isinstance(state_gauge, dict) and state_gauge.get("value"):
        rows.append(("opt.state_bytes_per_rank", state_gauge["value"]))
    fused = obj.get("histograms", {}).get("opt.fused_update_ms")
    if isinstance(fused, dict) and fused.get("count"):
        rows.append(("opt.fused_updates", fused["count"]))
        rows.append(("opt.fused_update_ms_avg",
                     round(fused.get("sum", 0.0) / fused["count"], 3)))
    # the chosen comm schedule (autotuner winner or checkpoint-restored):
    # bucket cap + flush policy as one human row (ISSUE 19)
    gauges = obj.get("gauges", {})
    cap_g = gauges.get("comm.schedule.bucket_mb")
    if isinstance(cap_g, dict) and cap_g.get("value") is not None:
        ready_g = gauges.get("comm.schedule.ready", {})
        policy = "ready" if (isinstance(ready_g, dict)
                             and ready_g.get("value")) else "registration"
        rows.append(("comm.schedule",
                     "%gMB/%s" % (cap_g["value"], policy)))
    sweep_g = gauges.get("comm.autotune.sweep_steps")
    if isinstance(sweep_g, dict) and sweep_g.get("value") is not None:
        rows.append(("comm.autotune.sweep_steps", int(sweep_g["value"])))
    buckets = counters.get("comm.bucket.count", 0)
    if buckets:
        rows.append(("avg_bucket_kb",
                     round(counters.get("comm.bucket.bytes", 0)
                           / buckets / 1024.0, 1)))
    pushes = counters.get("kvstore.push_calls", 0)
    if pushes:
        rows.append(("collectives_per_push",
                     round(counters.get("comm.collectives", 0)
                           / float(pushes), 2)))
    return rows


def _print_comm(rows, fmt):
    if not rows:
        print("no comm.*/kvstore.* counters in this dump (no gradient "
              "sync ran, or telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| metric | value |")
        print("| --- | --- |")
        line = "| %s | %s |"
    else:
        print("metric,value")
        line = "%s,%s"
    for r in rows:
        print(line % r)


def parse_flight(obj):
    """Flatten a flight-recorder dump (`telemetry.flight.dump()` JSON, or a
    dict with a "records" list) into per-step rows:
    [(seq, site, step_ms, anomalies, compiles, events, notes)]."""
    records = obj.get("records", [])
    rows = []
    for r in records:
        deltas = r.get("deltas", {})
        notes = []
        for key, label in (("comm.collectives", "coll"),
                           ("comm.bucket.bytes", "comm_B"),
                           ("resilience.restores", "restores"),
                           ("resilience.retries", "retries")):
            if key in deltas:
                notes.append("%s=%s" % (label, deltas[key]))
        if r.get("retrace_reasons"):
            notes.append("retrace: " + "; ".join(r["retrace_reasons"]))
        rows.append((r.get("seq", ""), r.get("site", "?"),
                     r.get("step_ms", ""),
                     ",".join(r.get("anomalies", [])),
                     ",".join(r.get("compiles", [])),
                     "; ".join(r.get("events", [])),
                     " ".join(notes)))
    return rows


def _print_flight(rows, fmt):
    if not rows:
        print("no flight-recorder records in this dump", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| step | site | step_ms | anomalies | compiles | events |"
              " notes |")
        print("| --- | --- | --- | --- | --- | --- | --- |")
        line = "| %s | %s | %s | %s | %s | %s | %s |"
    else:
        print("step,site,step_ms,anomalies,compiles,events,notes")
        line = "%s,%s,%s,%s,%s,%s,%s"
    for r in rows:
        if fmt == "csv":
            r = tuple(str(c).replace(",", ";") for c in r)
        print(line % r)


def parse_anomalies(obj):
    """Extract the anomaly story from a telemetry snapshot: every
    `telemetry.anomaly.*` counter plus the step-time histograms the spikes
    were judged against. Returns [(metric, kind, value)]."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    rows = []
    for name, v in sorted(obj.get("counters", {}).items()):
        if name.startswith("telemetry.anomaly."):
            rows.append((name[len("telemetry.anomaly."):], "count", v))
    for name, h in sorted(obj.get("histograms", {}).items()):
        if name.endswith(".step_ms"):
            avg = h.get("avg")
            rows.append((name, "avg_ms",
                         round(avg, 3) if avg is not None else ""))
            rows.append((name, "max_ms", h.get("max")))
    return rows


def _print_anomalies(rows, fmt):
    if not rows:
        print("no telemetry.anomaly.* counters in this dump (clean run, "
              "no steps, or telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| metric | kind | value |")
        print("| --- | --- | --- |")
        line = "| %s | %s | %s |"
    else:
        print("metric,kind,value")
        line = "%s,%s,%s"
    for r in rows:
        print(line % r)


def _hist_quantile(h, q):
    """Quantile estimate from a snapshot histogram's sparse PER-BUCKET
    counts (non-cumulative — `Histogram.snapshot()` format, not the
    cumulative `le` series of a Prometheus scrape). Stdlib re-derivation
    of telemetry.export.histogram_quantiles — this tool must run without
    mxnet_tpu importable."""
    count = h.get("count") or 0
    if not count:
        return None
    buckets = h.get("buckets", {})
    bounds = h.get("bounds")
    if bounds:
        # densify: an empty (omitted) bucket's bound can be the true
        # lower edge of the rank-holding bucket
        items = [(float(b), buckets.get("le_%g" % b, 0)) for b in bounds]
        items.append((float("inf"), buckets.get("le_inf", 0)))
    else:  # legacy dump without bounds
        items = []
        for key, n in buckets.items():
            raw = key[len("le_"):]
            items.append((float("inf") if raw == "inf" else float(raw), n))
        items.sort()
    target = q * count
    cum = 0
    lower = 0.0
    val = None
    for bound, n in items:
        if cum + n >= target:
            val = (h.get("max") if bound == float("inf")
                   else lower + (bound - lower) * (target - cum) / n)
            break
        cum += n
        if bound != float("inf"):
            lower = bound
    if val is None:
        val = h.get("max")
    if val is None:
        return None
    if h.get("min") is not None:
        val = max(val, h["min"])
    if h.get("max") is not None:
        val = min(val, h["max"])
    return round(val, 3)


# the serving headline, in client-experience order: traffic in, prompt
# work (chunked prefill + prefix reuse), decode (incl. speculation),
# latency felt, pressure and shedding, recovery churn
_SERVE_COUNTERS = ("requests", "admitted", "completed", "tokens",
                   "prefills", "prefill_chunks", "prefill_chunk_tokens",
                   "prefix", "decode_steps", "spec", "shed", "failed",
                   "recoveries", "requeued_streams", "compile", "retrace")


def parse_serve(obj):
    """Extract the serving story from a telemetry snapshot: serve.*
    counters (chunked-prefill, prefix-sharing, and speculative-decoding
    columns included), derived prefix_hit_rate / spec_accept_rate,
    TTFT/TPOT quantiles from the latency histograms, and the pressure
    gauges (queue depth, batch occupancy, KV-pool blocks).
    Returns [(metric, value)] rows."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    gauges = obj.get("gauges", {})
    hists = obj.get("histograms", {})
    rows = []
    tps = gauges.get("serve.tokens_per_s")
    if tps is not None:
        rows.append(("tokens_per_s", tps.get("value")))
    for name in _SERVE_COUNTERS:
        key = "serve.%s" % name
        if key in counters:
            rows.append((name, counters[key]))
        prefix = key + "."
        for sub in sorted(counters):
            if sub.startswith(prefix):
                rows.append((sub[len("serve."):], counters[sub]))
    lookups = counters.get("serve.prefix.lookups", 0)
    if lookups:
        rows.append(("prefix_hit_rate",
                     round(counters.get("serve.prefix.hits", 0)
                           / lookups, 4)))
    drafted = counters.get("serve.spec.drafted", 0)
    if drafted:
        rows.append(("spec_accept_rate",
                     round(counters.get("serve.spec.accepted", 0)
                           / drafted, 4)))
    for hname, label in (("serve.ttft_ms", "ttft_ms"),
                         ("serve.tpot_ms", "tpot_ms"),
                         ("serve.step_ms", "step_ms"),
                         ("serve.prefill_ms", "prefill_ms")):
        h = hists.get(hname)
        if h:
            rows.append((label + "_p50", _hist_quantile(h, 0.50)))
            rows.append((label + "_p99", _hist_quantile(h, 0.99)))
    for gname, label in (("serve.queue_depth", "queue_depth"),
                         ("serve.batch_occupancy", "batch_occupancy"),
                         ("serve.kv.blocks_in_use", "kv_blocks_in_use"),
                         ("serve.prefix.blocks", "prefix_cache_blocks"),
                         ("serve.replicas_alive", "replicas_alive")):
        g = gauges.get(gname)
        if g is not None:
            rows.append((label, g.get("value")))
            rows.append((label + "_peak", g.get("max")))
    return rows


def _print_serve(rows, fmt):
    if not rows:
        print("no serve.* metrics in this dump (no serving ran, or "
              "telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| metric | value |")
        print("| --- | --- |")
        line = "| %s | %s |"
    else:
        print("metric,value")
        line = "%s,%s"
    for r in rows:
        print(line % r)


# the sparse-embedding headline, in data-path order: training pushes
# (dedup ratio), the sparse wire (unique-rows comm vs the densified
# equivalent), the scatter-add kernel, and the served lookup path
_SPARSE_COUNTERS = ("embedding.push", "embedding.push.rows",
                    "embedding.push.unique_rows", "embedding.lookup",
                    "embedding.lookup.rows", "embedding.serve.lookup",
                    "embedding.serve.rows", "comm.sparse.push",
                    "comm.sparse.rows", "comm.sparse.unique_rows",
                    "comm.sparse.sync", "comm.sparse.bytes",
                    "comm.sparse.bytes_dense_equiv",
                    "comm.sparse.all_gather_rows",
                    "comm.sparse.psum_unique_rows",
                    "comm.sparse.bucket.count", "comm.sparse.bucket.bytes",
                    "comm.sparse.bucket.skipped")


def parse_sparse(obj):
    """Extract the sparse-embedding story (ISSUE 17) from a telemetry
    snapshot: embedding.* / comm.sparse.* counters, the derived
    unique-rows ratio (what fraction of pushed rows survived dedup),
    modeled wire savings vs the densified-allreduce equivalent,
    segment-sum kernel dispatch/fallback counts, served-lookup latency
    quantiles, and the table's HBM-ledger bytes.
    Returns [(metric, value)] rows."""
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    rows = []
    for name in _SPARSE_COUNTERS:
        if name in counters:
            rows.append((name, counters[name]))
    for name in sorted(counters):
        if name.startswith("comm.sparse.bucket.flush_reason."):
            rows.append((name, counters[name]))
    pushed = counters.get("comm.sparse.rows",
                          counters.get("embedding.push.rows", 0))
    unique = counters.get("comm.sparse.unique_rows",
                          counters.get("embedding.push.unique_rows", 0))
    if pushed:
        rows.append(("unique_rows_ratio", round(unique / pushed, 4)))
    dense_eq = counters.get("comm.sparse.bytes_dense_equiv", 0)
    sparse_b = counters.get("comm.sparse.bytes", 0)
    if dense_eq:
        rows.append(("comm_bytes_saved", dense_eq - sparse_b))
    disp = counters.get("ops.pallas.dispatch.segment_sum", 0)
    fall = sum(v for k, v in counters.items()
               if k.startswith("ops.pallas.fallback.segment_sum."))
    if disp or fall:
        rows.append(("segment_sum_dispatch", disp))
        rows.append(("segment_sum_fallback", fall))
    h = obj.get("histograms", {}).get("embedding.serve.lookup_ms")
    if isinstance(h, dict) and h.get("count"):
        rows.append(("serve_lookup_ms_p50", _hist_quantile(h, 0.50)))
        rows.append(("serve_lookup_ms_p99", _hist_quantile(h, 0.99)))
    g = obj.get("gauges", {}).get("memory.scope.embedding.bytes")
    if isinstance(g, dict) and g.get("value") is not None:
        rows.append(("table_bytes", g["value"]))
    return rows


def _print_sparse(rows, fmt):
    if not rows:
        print("no embedding.*/comm.sparse.* counters in this dump (no "
              "sparse embedding ran, or telemetry disabled)",
              file=sys.stderr)
        return
    if fmt == "markdown":
        print("| metric | value |")
        print("| --- | --- |")
        line = "| %s | %s |"
    else:
        print("metric,value")
        line = "%s,%s"
    for r in rows:
        print(line % r)


def parse_kernels(obj):
    """Extract the Pallas kernel-layer story (ISSUE 10): which stages ran
    fused (`ops.pallas.dispatch.<kernel>`), which calls fell back and WHY
    (`ops.pallas.fallback.<reason>` / `.<kernel>.<reason>`), how many
    kernels each compiled step program carries (`*.pallas_kernels`
    gauges), and the fused-update latency histogram.
    Returns [(kind, name, value)] rows."""
    rows = []
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    for total in ("ops.pallas.dispatch", "ops.pallas.fallback"):
        kind = total.rsplit(".", 1)[-1]
        if total in counters:
            rows.append((kind, "total", counters[total]))
        prefix = total + "."
        for name in sorted(counters):
            if name.startswith(prefix):
                rows.append((kind, name[len(prefix):], counters[name]))
    for gname in ("fused_step.pallas_kernels", "train_step.pallas_kernels"):
        g = obj.get("gauges", {}).get(gname)
        if isinstance(g, dict) and g.get("value") is not None:
            rows.append(("program", gname, g["value"]))
    fused = obj.get("histograms", {}).get("opt.fused_update_ms")
    if isinstance(fused, dict) and fused.get("count"):
        rows.append(("latency", "fused_updates", fused["count"]))
        rows.append(("latency", "fused_update_ms_avg",
                     round(fused.get("sum", 0.0) / fused["count"], 3)))
        rows.append(("latency", "fused_update_ms_max", fused.get("max")))
    return rows


def _print_kernels(rows, fmt):
    if not rows:
        print("no ops.pallas.* counters in this dump (no Pallas dispatch "
              "ran, or telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| kind | name | value |")
        print("| --- | --- | --- |")
        line = "| %s | %s | %s |"
    else:
        print("kind,name,value")
        line = "%s,%s,%s"
    for r in rows:
        print(line % r)


def parse_compile(obj):
    """Extract the whole-graph-compiler / AOT-cache story (ISSUE 11):
    how many graphs lowered and compiled, what the graph passes removed,
    cache hits/misses/writes/corruption, which executors fell back to
    op-by-op dispatch and WHY, plus per-site compile counters and the
    lower/compile latency histograms. Accepts a telemetry JSON dump or a
    `telemetry.compile_report()` dict (adds the recent-compiles ring
    rows). Returns [(kind, name, value)]."""
    rows = []
    ring = obj.get("recent_compiles")
    if "telemetry" in obj and isinstance(obj["telemetry"], dict):
        obj = obj["telemetry"]
    counters = obj.get("counters", {})
    for name in ("compiler.lower", "compiler.compile",
                 "compiler.program_runs"):
        if name in counters:
            rows.append(("compiler", name.split(".", 1)[1], counters[name]))
    for name in sorted(counters):
        if name.startswith("compiler.pass."):
            rows.append(("pass", name[len("compiler.pass."):],
                         counters[name]))
    for name in ("hits", "misses", "writes", "corrupt", "evictions",
                 "serialize_error", "write_error", "unusable",
                 "skipped_donated"):
        full = "compiler.cache." + name
        if full in counters:
            rows.append(("cache", name, counters[full]))
    if "compiler.fallback" in counters:
        rows.append(("fallback", "total", counters["compiler.fallback"]))
    for name in sorted(counters):
        if name.startswith("compiler.fallback."):
            rows.append(("fallback", name[len("compiler.fallback."):],
                         counters[name]))
    for site in ("cachedop.compile", "fused_step.compile",
                 "train_step.compile", "serve.compile", "cachedop.retrace",
                 "fused_step.retrace", "train_step.retrace", "serve.retrace",
                 "train_step.aot_restored", "fused_step.aot_restored"):
        if site in counters:
            rows.append(("site", site, counters[site]))
    for hname in ("compiler.lower_ms", "compiler.compile_ms",
                  "compiler.cache.load_ms", "compiler.cache.store_ms"):
        h = obj.get("histograms", {}).get(hname)
        if isinstance(h, dict) and h.get("count"):
            rows.append(("latency", hname + "_avg",
                         round(h.get("sum", 0.0) / h["count"], 3)))
            rows.append(("latency", hname + "_max", h.get("max")))
    if ring:
        for name, ts in ring:
            rows.append(("ring", name, ts))
    return rows


def _print_compile(rows, fmt):
    if not rows:
        print("no compiler.* counters in this dump (whole-graph compiler "
              "never ran, or telemetry disabled)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| kind | name | value |")
        print("| --- | --- | --- |")
        line = "| %s | %s | %s |"
    else:
        print("kind,name,value")
        line = "%s,%s,%s"
    for r in rows:
        print(line % r)


def parse_requests(obj):
    """Flatten a per-request trace dump — the `/requests` endpoint body
    ({"requests": [...]}) or a bare `telemetry.request_traces()` list —
    into [(request, outcome, wall_ms, queue_ms, prefill_ms, decode_ms,
    recovery_ms, ttft_ms, tokens, requeues, acct_pct)] rows."""
    if isinstance(obj, dict):
        reqs = obj.get("requests", [])
    else:
        reqs = obj or []
    rows = []
    for r in reqs:
        phases = r.get("phases_ms", {})
        wall = r.get("wall_ms") or 0.0
        acct = r.get("accounted_ms")
        acct_pct = (round(100.0 * acct / wall, 1)
                    if acct is not None and wall else "")
        rows.append((r.get("request_id", "?"), r.get("outcome", "?"),
                     wall, phases.get("queue", 0.0),
                     phases.get("prefill", 0.0), phases.get("decode", 0.0),
                     phases.get("recovery", 0.0),
                     r.get("ttft_ms") if r.get("ttft_ms") is not None
                     else "",
                     r.get("tokens", ""), r.get("requeues", 0), acct_pct))
    return rows


def _print_requests(rows, fmt):
    if not rows:
        print("no request traces in this dump (nothing served, or "
              "telemetry disabled)", file=sys.stderr)
        return
    header = ("request", "outcome", "wall_ms", "queue_ms", "prefill_ms",
              "decode_ms", "recovery_ms", "ttft_ms", "tokens", "requeues",
              "acct_pct")
    if fmt == "markdown":
        print("| " + " | ".join(header) + " |")
        print("|" + " --- |" * len(header))
        line = "| " + " | ".join(["%s"] * len(header)) + " |"
    else:
        print(",".join(header))
        line = ",".join(["%s"] * len(header))
    for r in rows:
        print(line % r)


# span categories for the --overlap decomposition (stdlib re-derivation of
# mxnet_tpu.telemetry.attribution — this tool must run without mxnet_tpu
# importable; keep the category sets in sync)
_OVL_COMM = ("comm",)
_OVL_HOST = ("host", "resilience", "fault", "user")
_OVL_IDLE = ("idle",)


def _ovl_union(iv):
    if not iv:
        return 0.0, []
    iv = sorted(iv)
    merged = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def _ovl_subtract(iv, cover):
    out = []
    for s, e in iv:
        cur = s
        for cs, ce in cover:
            if ce <= cur:
                continue
            if cs >= e:
                break
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def _trace_events(obj):
    """Span events as (name, cat, ts_s, dur_s) from either a chrome trace
    dump (`telemetry.dump_trace()`: traceEvents, µs) or a raw
    `local_trace_dump()` object (events, s)."""
    if "traceEvents" in obj:
        out = []
        for e in obj["traceEvents"]:
            if e.get("ph") != "X":
                continue
            out.append((e.get("name", "?"), e.get("cat", ""),
                        e.get("ts", 0.0) / 1e6, e.get("dur", 0.0) / 1e6))
        return out
    return [(n, c, ts, dur)
            for n, c, ts, dur, *_ in obj.get("events", [])]


def parse_overlap(obj, site=None):
    """Per-step compute/collective/host/idle decomposition + comm overlap
    fraction from a trace dump: one row per cat-``step`` span, plus a
    TOTAL row. Returns [(step, site, step_ms, compute_ms, collective_ms,
    host_ms, idle_ms, comm_n, overlap_frac)]."""
    events = _trace_events(obj)
    steps = [(n, ts, dur) for n, c, ts, dur in events
             if c == "step" and (site is None or n == site)]
    rows = []
    totals = {"step": 0.0, "compute": 0.0, "coll": 0.0, "host": 0.0,
              "idle": 0.0, "n_comm": 0}
    phase_total = overlap_weighted = 0.0
    for i, (name, t0, dur) in enumerate(steps):
        t1 = t0 + dur

        def clip(cats):
            out = []
            for _n, c, ts, d in events:
                if c not in cats:
                    continue
                s, e = max(ts, t0), min(ts + d, t1)
                if e > s:
                    out.append((s, e))
            return out

        comm_iv = clip(_OVL_COMM)
        coll, comm_cover = _ovl_union(comm_iv)
        host, host_cover = _ovl_union(
            _ovl_subtract(clip(_OVL_HOST), comm_cover))
        idle, _ = _ovl_union(_ovl_subtract(
            _ovl_subtract(clip(_OVL_IDLE), comm_cover), host_cover))
        compute = max(0.0, (t1 - t0) - coll - host - idle)
        ovl = ""
        if comm_iv:
            phase0 = min(s for s, _e in comm_iv)
            phase = t1 - phase0
            in_phase, _ = _ovl_union([(max(s, phase0), e)
                                      for s, e in comm_iv])
            if phase > 0:
                ovl = round(max(0.0, phase - in_phase) / phase, 4)
                phase_total += phase
                overlap_weighted += ovl * phase
        rows.append((i, name, round(dur * 1e3, 3),
                     round(compute * 1e3, 3), round(coll * 1e3, 3),
                     round(host * 1e3, 3), round(idle * 1e3, 3),
                     len(comm_iv), ovl))
        totals["step"] += dur
        totals["compute"] += compute
        totals["coll"] += coll
        totals["host"] += host
        totals["idle"] += idle
        totals["n_comm"] += len(comm_iv)
    if rows:
        rows.append(("TOTAL", site or "*", round(totals["step"] * 1e3, 3),
                     round(totals["compute"] * 1e3, 3),
                     round(totals["coll"] * 1e3, 3),
                     round(totals["host"] * 1e3, 3),
                     round(totals["idle"] * 1e3, 3), totals["n_comm"],
                     round(overlap_weighted / phase_total, 4)
                     if phase_total else ""))
    return rows


def _print_overlap(rows, fmt):
    if not rows:
        print("no step spans in this trace dump (record steps — trainer/"
              "fused_step/serve.step — or pass a merged dump)",
              file=sys.stderr)
        return
    header = ("step", "site", "step_ms", "compute_ms", "collective_ms",
              "host_ms", "idle_ms", "comm_n", "overlap_frac")
    if fmt == "markdown":
        print("| " + " | ".join(header) + " |")
        print("|" + " --- |" * len(header))
        line = "| " + " | ".join(["%s"] * len(header)) + " |"
    else:
        print(",".join(header))
        line = ",".join(["%s"] * len(header))
    for r in rows:
        print(line % r)


# severity ordering for the lint table: errors first, then by location
_LINT_SEV_ORDER = {"error": 0, "warning": 1, "info": 2}

# rule id -> short name for the rollup (static mirror of
# `mxnet_tpu.analysis.rule_table()` — this tool parses dumps offline and
# must not import the package)
_LINT_RULE_NAMES = {
    "TPU001": "host-sync-under-trace",
    "TPU002": "side-effect-under-trace",
    "TPU003": "data-dependent-control-flow",
    "TPU004": "retrace-hazard",
    "TPU005": "host-rng-under-trace",
    "TPU006": "thread-shared-state",
    "TPU007": "sharding-annotation",
    "TPU008": "collective-safety",
    "TPU009": "lock-order-inversion",
    "TPU010": "blocking-under-lock",
}


def parse_lint(obj):
    """Flatten tracelint JSON (`python -m mxnet_tpu.analysis --format
    json`) into [(severity, code, location, symbol, message)] rows,
    errors first."""
    findings = obj.get("findings", [])
    keyed = []
    for f in findings:
        fname = f.get("file", "?")
        try:
            line = int(f.get("line", 0))
        except (TypeError, ValueError):
            line = 0
        row = (f.get("severity", "?"), f.get("code", "?"),
               "%s:%d" % (fname, line), f.get("symbol", ""),
               f.get("message", ""))
        keyed.append(((_LINT_SEV_ORDER.get(row[0], 3), fname, line,
                       row[1]), row))
    keyed.sort(key=lambda kr: kr[0])
    return [row for _, row in keyed]


def _print_lint(rows, fmt):
    if not rows:
        print("no tracelint findings in this dump (clean tree)",
              file=sys.stderr)
        return
    if fmt == "markdown":
        print("| severity | code | location | symbol | message |")
        print("| --- | --- | --- | --- | --- |")
        line = "| %s | %s | %s | %s | %s |"
    else:
        print("severity,code,location,symbol,message")
        line = "%s,%s,%s,%s,%s"
    for r in rows:
        sev, code, loc, sym, msg = r
        if fmt == "csv":
            msg = msg.replace(",", ";")
        print(line % (sev, code, loc, sym, msg))
    if fmt != "markdown":
        return  # csv consumers want ONE table; the rollup is human-facing
    # per-rule rollup: which rule dominates the findings?
    by_rule = {}
    for sev, code, _loc, _sym, _msg in rows:
        key = (code, sev)
        by_rule[key] = by_rule.get(key, 0) + 1
    print()
    print("| rule | name | severity | count |")
    print("| --- | --- | --- | --- |")
    for code, sev in sorted(by_rule):
        print("| %s | %s | %s | %d |"
              % (code, _LINT_RULE_NAMES.get(code, "?"), sev,
                 by_rule[(code, sev)]))


_OVERLAY_SCOPES = ("prefix_cache",)   # bytes shared with another scope


def _mem_fmt_bytes(n):
    n = float(n)
    sign = "-" if n < 0 else ""
    n = abs(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return ("%s%.1f%s" % (sign, n, unit) if unit != "B"
                    else "%s%d%s" % (sign, int(n), unit))
        n /= 1024.0


def parse_mem(obj):
    """Extract the HBM-ledger story from a `/snapshot` payload (its
    ``memory`` block: per-scope bytes, per-program static footprints, the
    last reconcile) or from a bare telemetry snapshot (the
    ``memory.scope.<name>.bytes`` gauges). Returns
    ``(scope_rows, program_rows, reconcile_dict_or_None)`` where
    scope_rows = [(scope, bytes, note)] largest first and program_rows =
    [(label, origin, bytes, temp, code, args, out)]."""
    mem = obj.get("memory") if isinstance(obj.get("memory"), dict) else None
    scopes, programs, reconcile = {}, [], None
    if mem:
        scopes = {k: v for k, v in (mem.get("scopes") or {}).items()
                  if isinstance(v, (int, float))}
        programs = [p for p in (mem.get("programs") or [])
                    if isinstance(p, dict)]
        reconcile = mem.get("reconcile") or None
    else:
        tel = obj.get("telemetry") if isinstance(obj.get("telemetry"),
                                                 dict) else obj
        gauges = tel.get("gauges", {}) if isinstance(tel, dict) else {}
        for name, g in gauges.items():
            if (name.startswith("memory.scope.")
                    and name.endswith(".bytes")):
                scope = name[len("memory.scope."):-len(".bytes")]
                val = g.get("value") if isinstance(g, dict) else g
                if isinstance(val, (int, float)):
                    scopes[scope] = val
    scope_rows = []
    for name, val in sorted(scopes.items(), key=lambda kv: -abs(kv[1])):
        note = ""
        if name in _OVERLAY_SCOPES:
            note = "overlay (inside kv_pool)"
        elif name == "unattributed":
            note = "reconcile residual"
        scope_rows.append((name, int(val), note))
    program_rows = []
    for p in programs:
        program_rows.append((p.get("label", "?"),
                             "cache" if p.get("cached") else "compile",
                             int(p.get("bytes", 0)),
                             int(p.get("temp_bytes", 0)),
                             int(p.get("code_bytes", 0)),
                             int(p.get("argument_bytes", 0)),
                             int(p.get("output_bytes", 0))))
    program_rows.sort(key=lambda r: -r[2])
    return scope_rows, program_rows, reconcile


def _print_mem(parsed, fmt):
    scope_rows, program_rows, reconcile = parsed
    if not scope_rows and not program_rows:
        print("no memory-ledger data in this dump (ledger disabled, or "
              "not a /snapshot payload)", file=sys.stderr)
        return
    if fmt == "markdown":
        print("| scope | bytes | size | note |")
        print("| --- | --- | --- | --- |")
        line = "| %s | %d | %s | %s |"
    else:
        print("scope,bytes,size,note")
        line = "%s,%d,%s,%s"
    for name, val, note in scope_rows:
        print(line % (name, val, _mem_fmt_bytes(val), note))
    if reconcile and fmt == "markdown":
        print()
        print("reconcile: device=%s scoped=%s residual=%s (source: %s, "
              "%s device(s))"
              % (_mem_fmt_bytes(reconcile.get("device_bytes", 0)),
                 _mem_fmt_bytes(reconcile.get("scoped_bytes", 0)),
                 _mem_fmt_bytes(reconcile.get("residual_bytes", 0)),
                 reconcile.get("source", "?"),
                 reconcile.get("device_count", "?")))
    if not program_rows:
        return
    if fmt == "markdown":
        print()
        print("| program | origin | bytes | temp | code | args | out |")
        print("| --- | --- | --- | --- | --- | --- | --- |")
        pline = "| %s | %s | %s | %s | %s | %s | %s |"
    else:
        print("program,origin,bytes,temp,code,args,out")
        pline = "%s,%s,%s,%s,%s,%s,%s"
    for label, origin, total, temp, code, argb, outb in program_rows:
        print(pline % (label, origin, _mem_fmt_bytes(total),
                       _mem_fmt_bytes(temp), _mem_fmt_bytes(code),
                       _mem_fmt_bytes(argb), _mem_fmt_bytes(outb)))


def _load_json(path):
    try:
        with open(path) as f:
            obj = json.load(f)
        return obj if isinstance(obj, dict) else None
    except (ValueError, OSError):
        return None


def main():
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("logfile")
    parser.add_argument("--format", choices=["markdown", "csv"],
                        default="markdown")
    parser.add_argument("--metric", default="accuracy")
    parser.add_argument("--telemetry", action="store_true",
                        help="treat the input as a telemetry/profiler JSON "
                             "dump (auto-detected for JSON files)")
    parser.add_argument("--resilience", action="store_true",
                        help="resilience-events mode: table of retries/"
                             "stalls/restores/faults plus the integrity "
                             "plane (rollbacks, skipped batches, sentinel "
                             "divergences, corrupt-checkpoint fallbacks) "
                             "from a telemetry JSON dump — distinguishes a "
                             "noisy-but-recovered run from a clean one")
    parser.add_argument("--lint", action="store_true",
                        help="tracelint mode: table of findings from "
                             "`python -m mxnet_tpu.analysis --format json` "
                             "output, errors first")
    parser.add_argument("--comm", action="store_true",
                        help="gradient-comm mode: table of bucket/collective"
                             " counters from a telemetry JSON dump — was the"
                             " sync bucketed (few big launches) or per-param"
                             " (many small ones)?")
    parser.add_argument("--flight", action="store_true",
                        help="flight-recorder mode: per-step table from a "
                             "telemetry.flight.dump() JSON file — the last "
                             "N steps before a crash")
    parser.add_argument("--serve", action="store_true",
                        help="serving mode: tokens/s, ttft/tpot quantiles, "
                             "queue/batch/KV pressure, shed and recovery "
                             "counts from a telemetry JSON dump")
    parser.add_argument("--sparse", action="store_true",
                        help="sparse-embedding mode: embedding.*/"
                             "comm.sparse.* counters, unique-rows ratio, "
                             "modeled wire savings vs densified allreduce, "
                             "segment-sum dispatch/fallback counts, and "
                             "served-lookup latency quantiles from a "
                             "telemetry JSON dump")
    parser.add_argument("--kernels", action="store_true",
                        help="Pallas kernel-layer mode: dispatch/fallback "
                             "counts by kernel/reason, per-program fused-"
                             "kernel gauges and fused-update latency")
    parser.add_argument("--compile", dest="compile_mode",
                        action="store_true",
                        help="compiler mode: whole-graph lower/compile "
                             "counters, graph-pass stats, AOT-cache "
                             "hits/misses/corruption, op-by-op fallbacks "
                             "by reason, and the recent-compiles ring "
                             "from a telemetry JSON dump / "
                             "telemetry.compile_report()")
    parser.add_argument("--requests", dest="requests_mode",
                        action="store_true",
                        help="per-request trace mode: one row per served "
                             "request (ttft/queue-wait/prefill/decode/"
                             "recovery, outcome, requeues) from a "
                             "/requests endpoint dump or a "
                             "telemetry.request_traces() JSON list")
    parser.add_argument("--overlap", action="store_true",
                        help="comm-overlap attribution mode: per-step "
                             "compute/collective/host/idle decomposition "
                             "and the comm overlap fraction from a chrome "
                             "trace dump (telemetry.dump_trace output) — "
                             "the schedule autotuner's evidence table")
    parser.add_argument("--site", default=None,
                        help="with --overlap: only decompose step spans "
                             "with this name (e.g. serve.step)")
    parser.add_argument("--mem", action="store_true",
                        help="memory-ledger mode: per-scope HBM bytes, "
                             "per-program static footprints (compile vs "
                             "AOT-cache restore), and the device/scoped "
                             "reconcile from a /snapshot payload or a "
                             "telemetry JSON dump's memory.scope.* gauges")
    parser.add_argument("--anomalies", action="store_true",
                        help="anomaly mode: telemetry.anomaly.* counters + "
                             "step-time histograms from a telemetry JSON "
                             "dump — did any step blow its rolling median "
                             "or SLO?")
    args = parser.parse_args()
    obj = _load_json(args.logfile)
    if args.requests_mode:
        # a bare telemetry.request_traces() list is a valid input here,
        # which _load_json (dict-only) rejects — load it directly
        raw = None
        try:
            with open(args.logfile) as f:
                raw = json.load(f)
        except (ValueError, OSError):
            pass
        if not isinstance(raw, (dict, list)):
            sys.exit("--requests input is not JSON: %s" % args.logfile)
        _print_requests(parse_requests(raw), args.format)
        return
    if args.overlap:
        if obj is None:
            sys.exit("--overlap input is not a JSON object: %s"
                     % args.logfile)
        _print_overlap(parse_overlap(obj, site=args.site), args.format)
        return
    if args.compile_mode:
        if obj is None:
            sys.exit("--compile input is not a JSON object: %s"
                     % args.logfile)
        _print_compile(parse_compile(obj), args.format)
        return
    if args.serve:
        if obj is None:
            sys.exit("--serve input is not a JSON object: %s" % args.logfile)
        _print_serve(parse_serve(obj), args.format)
        return
    if args.mem:
        if obj is None:
            sys.exit("--mem input is not a JSON object: %s" % args.logfile)
        _print_mem(parse_mem(obj), args.format)
        return
    if args.flight:
        if obj is None:
            sys.exit("--flight input is not a JSON object: %s"
                     % args.logfile)
        _print_flight(parse_flight(obj), args.format)
        return
    if args.anomalies:
        if obj is None:
            sys.exit("--anomalies input is not a JSON object: %s"
                     % args.logfile)
        _print_anomalies(parse_anomalies(obj), args.format)
        return
    if args.kernels:
        if obj is None:
            sys.exit("--kernels input is not a JSON object: %s"
                     % args.logfile)
        _print_kernels(parse_kernels(obj), args.format)
        return
    if args.sparse:
        if obj is None:
            sys.exit("--sparse input is not a JSON object: %s"
                     % args.logfile)
        _print_sparse(parse_sparse(obj), args.format)
        return
    if args.comm:
        if obj is None:
            sys.exit("--comm input is not a JSON object: %s" % args.logfile)
        _print_comm(parse_comm(obj), args.format)
        return
    if args.lint:
        if obj is None:
            sys.exit("--lint input is not a JSON object: %s" % args.logfile)
        _print_lint(parse_lint(obj), args.format)
        return
    if args.resilience:
        if obj is None:
            sys.exit("--resilience input is not a JSON object: %s"
                     % args.logfile)
        _print_resilience(parse_resilience(obj), args.format)
        return
    if args.telemetry or obj is not None:
        if obj is None:
            sys.exit("--telemetry input is not a JSON object: %s"
                     % args.logfile)
        _print_telemetry(parse_telemetry(obj), args.format)
        return
    with open(args.logfile) as f:
        rows = parse(f, args.metric)
    if args.format == "markdown":
        print("| epoch | train-%s | val-%s | time(s) |" % (args.metric,
                                                           args.metric))
        print("| --- | --- | --- | --- |")
        fmt = "| %d | %s | %s | %s |"
    else:
        print("epoch,train-%s,val-%s,time" % (args.metric, args.metric))
        fmt = "%d,%s,%s,%s"
    for e in sorted(rows):
        r = rows[e]
        print(fmt % (e, r["train"], r["val"], r["time"]))


if __name__ == "__main__":
    main()
