"""HBM memory ledger: every byte attributed to the subsystem holding it.

The watermark sampler (`telemetry.memory`) answers "how much HBM is in
use"; pod-scale runs die on the question it cannot answer — "*whose*
bytes are they?". The ledger is a registry of named memory **scopes**,
fed by explicit `account()` calls at the allocation sites that already
exist:

======================  ====================================================
scope                   accounted by
======================  ====================================================
``params``              `ShardedTrainStep.init()` / `place()` (re-layout)
``optimizer``           `ZeroUpdater` state gauge / train-step opt state
``grad_buckets``        `engine.BucketLayout` (frozen flat-gradient layout)
``kv_pool``             `serve.KVBlockPool` storage (target model)
``kv_draft``            the draft model's mirrored pool     [spec decoding]
``prefix_cache``        prefix-index pinned blocks (OVERLAY: these bytes
                        live inside ``kv_pool`` storage and are excluded
                        from the reconcile sum)
``programs``            per-executable static footprints from
                        ``compiled.memory_analysis()`` (temp + generated
                        code), harvested at every compile/AOT-restore site
``unattributed``        the reconcile residual (see below)
======================  ====================================================

Per-program **static footprints** are harvested wherever an executable is
built or restored (`compiler/cache.load_or_compile`, the whole-graph
`GraphProgram.compiled`, serve warm-up, the sharded train step's AOT
path) via `harvest()` + `note_program()`. The footprint is stored INSIDE
the AOT cache entry's meta, so a warm restore reports the same numbers
without recompiling — the fleet cold-start path stays observable.

`reconcile()` compares the scoped total against the device's own story
(`Device.memory_stats()` where the backend has an allocator; the
`jax.live_arrays()` byte total as the CPU fallback): the residual is the
``unattributed`` scope — a growing residual means an allocation site the
ledger does not know about. `maybe_reconcile()` rate-limits to one probe
per `MIN_RECONCILE_S` so `step_event` can call it unconditionally.

Every scope exports a ``memory.scope.<name>.bytes`` gauge (→ `/metrics`,
`/snapshot`, the JSONL stream); `format_scopes()` renders the top-scopes
breakdown that OOM / `Overloaded(kv_exhausted)` / `StallError`
post-mortems embed; `check_budget()` validates a run against a declared
per-chip budget (what `tests/test_scale_8b.py` asserts of the 8B dryrun).

Gating: inert under ``MXNET_TPU_TELEMETRY=0`` (no state, no gauges) and
under ``MXNET_TPU_LEDGER=0`` (the bench A/B lever — telemetry stays up,
the ledger alone goes quiet).
"""
from __future__ import annotations

import os
import threading
import time

__all__ = ["account", "adjust", "scopes", "programs", "note_program",
           "harvest", "reconcile", "maybe_reconcile", "last_reconcile",
           "check_budget", "tree_nbytes", "format_scopes", "breakdown",
           "enabled", "reset", "SCOPES", "OVERLAY_SCOPES",
           "MIN_RECONCILE_S"]

# the canonical scope names (account() accepts others — a future subsystem
# should not need a ledger edit to be accountable)
SCOPES = ("params", "optimizer", "grad_buckets", "kv_pool", "kv_draft",
          "prefix_cache", "embedding", "programs", "unattributed")

# overlay scopes annotate bytes that ALREADY belong to another scope's
# allocation (prefix-cache blocks live inside kv_pool storage); they are
# reported but excluded from the reconcile sum, else sharing would be
# double-counted as allocation
OVERLAY_SCOPES = frozenset({"prefix_cache"})

MIN_RECONCILE_S = 1.0
_PROGRAM_LIMIT = 64     # newest-wins bound on the per-program table

_lock = threading.Lock()
_scopes = {}            # scope name -> bytes (absolute, set-semantics)
_programs = {}          # label -> footprint dict
_last = {"reconcile": None, "ts": 0.0}


def _telem():
    from .. import telemetry
    return telemetry


def enabled():
    """The ledger's own gate: telemetry on AND MXNET_TPU_LEDGER not off."""
    if not _telem().ENABLED:
        return False
    return os.environ.get("MXNET_TPU_LEDGER", "1").lower() not in (
        "0", "false", "off")


def _gauge(scope, nbytes):
    _telem().registry.gauge("memory.scope.%s.bytes" % scope).set(int(nbytes))


# ------------------------------------------------------------------ account
def account(scope, nbytes):
    """Set scope `scope`'s byte total (absolute — allocation sites know
    their own totals; there is no delta bookkeeping to drift). No-op when
    the ledger is disabled."""
    if not enabled():
        return
    nbytes = int(nbytes)
    with _lock:
        _scopes[str(scope)] = nbytes
    _gauge(scope, nbytes)


def adjust(scope, delta):
    """Add `delta` bytes to a scope (for sites that only know increments).
    Returns the new total, or None when disabled."""
    if not enabled():
        return None
    with _lock:
        total = _scopes.get(str(scope), 0) + int(delta)
        _scopes[str(scope)] = total
    _gauge(scope, total)
    return total


def scopes():
    """{scope: bytes} snapshot (includes overlay scopes and the last
    reconcile's ``unattributed`` residual); {} when disabled."""
    with _lock:
        return dict(_scopes)


def _scoped_total_locked():
    return sum(v for k, v in _scopes.items()
               if k not in OVERLAY_SCOPES and k != "unattributed")


# ----------------------------------------------------------------- programs
def harvest(compiled):
    """Best-effort static footprint of a `jax.stages.Compiled`:
    `memory_analysis()` sizes as a plain dict, or None when the backend
    does not expose them. Never raises — a footprint is evidence, not a
    dependency."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    out = {}
    for key, attr in (("temp_bytes", "temp_size_in_bytes"),
                      ("argument_bytes", "argument_size_in_bytes"),
                      ("output_bytes", "output_size_in_bytes"),
                      ("alias_bytes", "alias_size_in_bytes"),
                      ("code_bytes", "generated_code_size_in_bytes")):
        try:
            val = getattr(ma, attr, None)
        except Exception:
            val = None
        if val is not None:
            out[key] = int(val)
    if not out:
        return None
    # the bytes the program itself pins beyond its operands: XLA scratch +
    # generated code (arguments/outputs are the caller's arrays, already
    # accounted under their owning scopes)
    out["bytes"] = out.get("temp_bytes", 0) + out.get("code_bytes", 0)
    return out


def note_program(label, footprint, cached=False):
    """Record one executable's static footprint (newest wins per label) and
    refresh the ``programs`` scope = Σ(temp + generated code). `cached`
    marks an AOT-cache restore replaying the footprint stored at compile
    time. Tolerates footprint=None (backend without memory_analysis)."""
    if not enabled() or not footprint:
        return
    entry = dict(footprint)
    entry["label"] = str(label)
    entry["cached"] = bool(cached)
    with _lock:
        _programs[str(label)] = entry
        if len(_programs) > _PROGRAM_LIMIT:
            # drop the oldest insertion (dicts preserve order)
            _programs.pop(next(iter(_programs)))
        total = sum(p.get("bytes", 0) for p in _programs.values())
        _scopes["programs"] = total
    _gauge("programs", total)
    _telem().inc("ledger.programs.%s" % ("cached" if cached else "fresh"))


def programs():
    """Recorded per-program footprints, oldest first (list of dicts with
    label/cached/bytes/temp_bytes/...); [] when disabled."""
    with _lock:
        return [dict(p) for p in _programs.values()]


# ---------------------------------------------------------------- reconcile
def _device_bytes():
    """(total bytes, source, device count) from the backend: allocator
    stats where the platform has them, the live-array byte total as the
    CPU fallback, (0, "none", 0) when jax is absent."""
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return 0, "none", 0
    total = 0
    reported = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        in_use = (stats or {}).get("bytes_in_use")
        if in_use is not None:
            total += int(in_use)
            reported += 1
    if reported:
        return total, "memory_stats", len(devices)
    # CPU (or a backend without allocator stats): the live-array walk is
    # the only byte total available
    total = 0
    try:
        for arr in jax.live_arrays():
            nbytes = getattr(arr, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
    except Exception:
        return 0, "none", len(devices)
    return total, "live_arrays", len(devices)


def reconcile():
    """Compare the scoped total against the device's own byte count; the
    residual becomes the ``unattributed`` scope (gauged). Returns the
    reconcile dict ``{device_bytes, scoped_bytes, residual_bytes, source,
    device_count, ts}`` or None when disabled."""
    if not enabled():
        return None
    device_total, source, n_dev = _device_bytes()
    with _lock:
        scoped = _scoped_total_locked()
        residual = device_total - scoped if source != "none" else 0
        _scopes["unattributed"] = residual
        report = {
            "device_bytes": device_total,
            "scoped_bytes": scoped,
            "residual_bytes": residual,
            "source": source,
            "device_count": n_dev,
            "ts": time.time(),
        }
        _last["reconcile"] = report
        _last["ts"] = time.monotonic()
    _gauge("unattributed", residual)
    return dict(report)


def maybe_reconcile():
    """Rate-limited reconcile for per-step call sites (`step_event`)."""
    if not enabled():
        return None
    with _lock:
        due = time.monotonic() - _last["ts"] >= MIN_RECONCILE_S
    if not due:
        return None
    return reconcile()


def last_reconcile():
    """The most recent reconcile dict (None before the first)."""
    with _lock:
        report = _last["reconcile"]
    return dict(report) if report else None


# ------------------------------------------------------------------- budget
def check_budget(budget_bytes_per_chip, residual_tolerance=0.25):
    """Validate the run against a declared per-chip HBM budget:
    reconciles, then checks that (a) the per-chip device total fits the
    budget and (b) the per-scope breakdown sums to within
    ``residual_tolerance`` (a fraction of the device total) — i.e. the
    ledger actually explains the memory it budgets.

    Returns ``{ok, budget_bytes_per_chip, per_chip_bytes, device_bytes,
    scoped_bytes, residual_bytes, residual_frac, device_count, source,
    scopes, failures}``; never raises. ``ok`` is False when disabled
    (an unaccountable run cannot pass a budget check)."""
    report = reconcile()
    if report is None:
        return {"ok": False, "failures": ["ledger disabled"],
                "budget_bytes_per_chip": int(budget_bytes_per_chip),
                "scopes": {}}
    n_dev = max(1, report["device_count"])
    per_chip = report["device_bytes"] / n_dev
    denom = max(1, report["device_bytes"])
    residual_frac = abs(report["residual_bytes"]) / denom
    failures = []
    if report["source"] == "none":
        failures.append("no device byte source (jax unavailable)")
    if per_chip > int(budget_bytes_per_chip):
        failures.append(
            "per-chip bytes %d exceed budget %d"
            % (per_chip, int(budget_bytes_per_chip)))
    if residual_frac > float(residual_tolerance):
        failures.append(
            "unattributed residual %.1f%% of device total exceeds "
            "tolerance %.1f%%"
            % (residual_frac * 100, float(residual_tolerance) * 100))
    out = dict(report)
    out.update({
        "ok": not failures,
        "budget_bytes_per_chip": int(budget_bytes_per_chip),
        "per_chip_bytes": int(per_chip),
        "residual_frac": residual_frac,
        "scopes": scopes(),
        "failures": failures,
    })
    return out


# ---------------------------------------------------------------- rendering
def tree_nbytes(tree):
    """Total bytes of a pytree's array leaves (best-effort; 0 on failure
    — an accounting helper must never break the path it measures)."""
    try:
        import jax
        total = 0
        for leaf in jax.tree_util.tree_leaves(tree):
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is not None:
                total += int(nbytes)
        return total
    except Exception:
        return 0


def _fmt_bytes(n):
    n = float(n)
    sign = "-" if n < 0 else ""
    n = abs(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return "%s%.1f%s" % (sign, n, unit) if unit != "B" \
                else "%s%d%s" % (sign, int(n), unit)
        n /= 1024.0
    return "%s%.1fGiB" % (sign, n)


def breakdown(top=4):
    """One-line top-scopes summary for error messages:
    ``kv_pool=1.5GiB, params=1.2GiB, ... (scoped 3.1GiB)``. Empty string
    when the ledger is disabled or has nothing."""
    snap = scopes()
    ranked = sorted(((k, v) for k, v in snap.items()
                     if k != "unattributed" and v), key=lambda kv: -kv[1])
    if not ranked:
        return ""
    parts = ["%s=%s" % (k, _fmt_bytes(v)) for k, v in ranked[:top]]
    total = sum(v for k, v in snap.items()
                if k not in OVERLAY_SCOPES and k != "unattributed")
    return "%s (scoped %s)" % (", ".join(parts), _fmt_bytes(total))


def format_scopes():
    """Multi-line scope table for post-mortems (`StallError.format_report`
    embeds it): one line per scope, largest first, overlay scopes and the
    residual annotated."""
    snap = scopes()
    if not snap:
        return "memory ledger: empty"
    lines = ["memory ledger (per-scope bytes):"]
    for name, val in sorted(snap.items(), key=lambda kv: -abs(kv[1])):
        tag = ""
        if name in OVERLAY_SCOPES:
            tag = "  [overlay]"
        elif name == "unattributed":
            tag = "  [residual]"
        lines.append("  %-14s %12d  (%s)%s"
                     % (name, val, _fmt_bytes(val), tag))
    return "\n".join(lines)


def reset():
    """Drop every scope, program footprint, and reconcile record (does not
    change the enable gates)."""
    with _lock:
        _scopes.clear()
        _programs.clear()
        _last["reconcile"] = None
        _last["ts"] = 0.0
