"""Bench-history store: every bench.py row, appended forever, fingerprinted.

A bench number is only comparable to another bench number taken on the
SAME hardware and software stack — a CPU smoke row regressing against an
accelerator row is noise, not signal. So every row appended here carries
an *environment fingerprint*: platform, device count, jax/jaxlib/python
versions, and whether the run silently fell back to CPU. The regression
gate (`tools/check_bench.py`) only ever compares rows whose fingerprints
match.

Storage is one JSON object per line (`bench_history.jsonl`, next to this
repo's bench.py, overridable via ``MXNET_TPU_BENCH_HISTORY``) — append-only
so concurrent bench runs cannot corrupt each other, greppable, diffable,
and trivially committed to git so CI has a rolling baseline to gate on.

Stdlib-only: bench.py imports this *after* the backend probe, and CI
imports it from a bare checkout — it must never pull in jax or mxnet_tpu
(the fingerprint's jax versions come from the caller or from
importlib.metadata, never from importing jax).

Used two ways:
  - bench.py calls `append(row)` after printing its BENCH line;
  - `python tools/benchdb.py` pretty-prints the history grouped by
    (metric, fingerprint) for a human.
"""
import hashlib
import json
import os
import platform as _platform
import sys

__all__ = ["fingerprint", "fingerprint_id", "history_path", "append",
           "load"]


def _dist_version(name):
    """Installed-distribution version without importing the package (an
    `import jax` here would be the first, in a tool that needs no
    backend)."""
    try:
        from importlib import metadata
        return metadata.version(name)
    except Exception:  # noqa: BLE001 — absent dist, py<3.8, broken metadata
        return None


def fingerprint(backend=None, device_count=None, cpu_fallback=None):
    """The environment identity a bench row is only comparable within.

    The caller (bench.py) passes what it already knows — the backend
    platform and the device count — so this module never has to import
    jax itself. `cpu_fallback` has no caller since bench.py stopped
    falling back; the key stays because it is part of every recorded
    row's fingerprint id, and goes with bench.py (ROADMAP S0).
    """
    return {
        "backend": backend or "unknown",
        "device_count": int(device_count) if device_count else 0,
        "cpu_fallback": bool(cpu_fallback),
        "jax": _dist_version("jax"),
        "jaxlib": _dist_version("jaxlib"),
        "python": "%d.%d" % sys.version_info[:2],
        "machine": _platform.machine(),
        "system": _platform.system(),
    }


def fingerprint_id(fp):
    """Short stable id of a fingerprint dict — the grouping key the
    regression gate buckets history rows by."""
    canon = json.dumps(fp, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()[:12]


def history_path():
    env = os.environ.get("MXNET_TPU_BENCH_HISTORY")
    if env:
        return env
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench_history.jsonl")


def append(row, path=None):
    """Append one bench row (a dict) as a JSON line. Best-effort: a full
    disk or read-only checkout must not fail the bench itself. Returns
    the path written, or None."""
    path = path or history_path()
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
        return path
    except OSError as e:
        print("# benchdb: could not append to %s: %s" % (path, e),
              file=sys.stderr)
        return None


def load(path=None):
    """All rows, oldest first. Unparseable lines are skipped (a truncated
    tail from a killed run must not poison the whole history)."""
    path = path or history_path()
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    continue
                if isinstance(obj, dict):
                    rows.append(obj)
    except OSError:
        pass
    return rows


def _main(argv):
    path = argv[1] if len(argv) > 1 else history_path()
    rows = load(path)
    if not rows:
        print("no history at %s" % path)
        return 0
    groups = {}
    for row in rows:
        key = (row.get("metric", "?"), row.get("fingerprint_id", "?"))
        groups.setdefault(key, []).append(row)
    print("%s: %d rows, %d (metric, fingerprint) series"
          % (path, len(rows), len(groups)))
    for (metric, fpid), series in sorted(groups.items()):
        vals = [r.get("value") for r in series if r.get("value") is not None]
        tail = ", ".join("%g" % v for v in vals[-5:])
        print("  %-40s fp=%s n=%-3d last: %s"
              % (metric, fpid, len(series), tail))
    return 0


if __name__ == "__main__":
    sys.exit(_main(sys.argv))
