"""Memory-observability plane tests (ISSUE 16).

Covers the HBM ledger (scope accounting, overlay exclusion, reconcile
residual math, per-chip budget checks, per-program static footprints on
both cold compile and warm AOT-cache restore), the on-demand profiling
endpoint (capture + rate limiting + full inertness under
``MXNET_TPU_TELEMETRY=0``), the serve KV byte gauges and the ledger
breakdown carried by `Overloaded(kv_exhausted)` / `StallError`, and the
tracelint cleanliness of every new module.
"""
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import export, ledger, profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.reset()
    (telemetry.enable if was_enabled else telemetry.disable)()


# ------------------------------------------------------------------ ledger
def test_account_and_scopes():
    ledger.account("params", 1000)
    ledger.account("kv_pool", 500)
    assert ledger.scopes() == {"params": 1000, "kv_pool": 500}
    # absolute set semantics: a re-account replaces, never accumulates
    ledger.account("params", 800)
    assert ledger.scopes()["params"] == 800
    # adjust() is the increment form
    assert ledger.adjust("params", 200) == 1000
    # every scope exports a gauge
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["memory.scope.params.bytes"]["value"] == 1000
    assert gauges["memory.scope.kv_pool.bytes"]["value"] == 500


def test_reconcile_residual_math(monkeypatch):
    """residual = device bytes - Σ(non-overlay scopes); prefix_cache is an
    overlay (its bytes live inside kv_pool storage) and must not be
    double-counted."""
    ledger.account("kv_pool", 600)
    ledger.account("prefix_cache", 400)   # overlay: inside kv_pool's 600
    ledger.account("params", 100)
    monkeypatch.setattr(ledger, "_device_bytes",
                        lambda: (1000, "memory_stats", 2))
    rep = ledger.reconcile()
    assert rep["scoped_bytes"] == 700     # 600 + 100, NOT + 400
    assert rep["residual_bytes"] == 300
    assert rep["source"] == "memory_stats"
    assert ledger.scopes()["unattributed"] == 300
    assert telemetry.snapshot()["gauges"][
        "memory.scope.unattributed.bytes"]["value"] == 300
    assert ledger.last_reconcile()["residual_bytes"] == 300


def test_check_budget_pass_and_fail(monkeypatch):
    ledger.account("params", 900)
    monkeypatch.setattr(ledger, "_device_bytes",
                        lambda: (1000, "memory_stats", 2))
    # 500 B/chip under a 1 KiB budget, residual 10% under 25% tolerance
    rep = ledger.check_budget(1024)
    assert rep["ok"], rep["failures"]
    assert rep["per_chip_bytes"] == 500
    assert rep["scopes"]["params"] == 900
    # budget violation
    rep = ledger.check_budget(400)
    assert not rep["ok"] and any("budget" in f for f in rep["failures"])
    # residual violation: the ledger explains only half the device bytes
    ledger.reset()
    ledger.account("params", 500)
    rep = ledger.check_budget(1024, residual_tolerance=0.25)
    assert not rep["ok"]
    assert any("residual" in f for f in rep["failures"])


def test_ledger_lever_disables_quietly(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_LEDGER", "0")
    assert not ledger.enabled()
    ledger.account("params", 123)
    assert ledger.scopes() == {}
    assert ledger.reconcile() is None
    assert "memory.scope.params.bytes" not in \
        telemetry.snapshot()["gauges"]
    rep = ledger.check_budget(1 << 30)
    assert not rep["ok"]                  # an unaccountable run can't pass


def test_breakdown_and_format_scopes():
    ledger.account("kv_pool", 3 << 30)
    ledger.account("params", 1 << 20)
    line = ledger.breakdown()
    assert "kv_pool=3.0GiB" in line and "scoped" in line
    table = ledger.format_scopes()
    assert "kv_pool" in table and "memory ledger" in table


def test_reset_clears_everything():
    ledger.account("params", 10)
    ledger.note_program("x", {"temp_bytes": 5, "bytes": 5})
    ledger.reset()
    assert ledger.scopes() == {}
    assert ledger.programs() == []
    assert ledger.last_reconcile() is None


# ---------------------------------------------------------- program ledger
def test_note_program_and_programs_scope():
    ledger.note_program("serve.decode", {"temp_bytes": 100, "code_bytes": 20,
                                         "bytes": 120})
    ledger.note_program("serve.chunk", {"temp_bytes": 50, "code_bytes": 10,
                                        "bytes": 60}, cached=True)
    progs = {p["label"]: p for p in ledger.programs()}
    assert progs["serve.decode"]["cached"] is False
    assert progs["serve.chunk"]["cached"] is True
    assert ledger.scopes()["programs"] == 180
    counters = telemetry.snapshot()["counters"]
    assert counters["ledger.programs.fresh"] == 1
    assert counters["ledger.programs.cached"] == 1
    # newest wins per label — no duplicate rows, scope total follows
    ledger.note_program("serve.decode", {"temp_bytes": 10, "bytes": 10})
    assert ledger.scopes()["programs"] == 70


def test_harvest_real_compiled():
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    fp = ledger.harvest(compiled)
    assert fp is not None
    assert fp["argument_bytes"] >= 8 * 8 * 4
    assert fp["bytes"] == fp.get("temp_bytes", 0) + fp.get("code_bytes", 0)


def test_footprint_cold_compile_and_warm_restore(tmp_path, monkeypatch):
    """Acceptance: an AOT-cached program reports its memory_analysis
    footprint on the cold compile AND on a warm cache restore — without
    recompiling (the footprint rides the cache entry's meta)."""
    from mxnet_tpu.compiler import cache as aotc
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))

    def lower():
        return jax.jit(lambda x: x * 2 + 1).lower(
            jnp.ones((16, 16), jnp.float32))

    key = aotc.cache_key(test="memobs_warm")
    _, was_cached = aotc.load_or_compile(key, lower, "memobs.prog")
    assert not was_cached
    cold = {p["label"]: p for p in ledger.programs()}["memobs.prog"]
    assert cold["cached"] is False

    # a fresh process would start with an empty ledger: simulate it
    ledger.reset()
    telemetry.reset()
    _, was_cached = aotc.load_or_compile(key, lower, "memobs.prog")
    assert was_cached
    warm = {p["label"]: p for p in ledger.programs()}["memobs.prog"]
    assert warm["cached"] is True
    # the warm restore replays the numbers recorded at compile time
    assert {k: v for k, v in warm.items() if k not in ("cached",)} == \
        {k: v for k, v in cold.items() if k not in ("cached",)}
    assert telemetry.snapshot()["counters"]["ledger.programs.cached"] == 1


def test_snapshot_payload_carries_memory_block():
    ledger.account("params", 2048)
    ledger.note_program("p1", {"temp_bytes": 7, "bytes": 7})
    ledger.reconcile()
    payload = export.snapshot_payload()
    mem = payload["memory"]
    assert mem["scopes"]["params"] == 2048
    assert mem["programs"][0]["label"] == "p1"
    assert mem["reconcile"]["scoped_bytes"] >= 2048
    assert "profiles" in payload


def test_step_event_reconciles_rate_limited(monkeypatch):
    monkeypatch.setattr(ledger, "_device_bytes",
                        lambda: (100, "memory_stats", 1))
    ledger.account("params", 60)
    telemetry.step_event("train", 1.0)
    assert ledger.last_reconcile() is not None
    assert ledger.scopes()["unattributed"] == 40
    # a second step inside MIN_RECONCILE_S must not probe again
    monkeypatch.setattr(ledger, "_device_bytes",
                        lambda: (999, "memory_stats", 1))
    telemetry.step_event("train", 1.0)
    assert ledger.scopes()["unattributed"] == 40


# ------------------------------------------------------------- serve wiring
def test_kv_pool_byte_gauges_and_overloaded_breakdown():
    from mxnet_tpu.models.llama import LlamaConfig
    from mxnet_tpu.serve.errors import Overloaded
    from mxnet_tpu.serve.kv_cache import KVBlockPool
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=64, dtype=jnp.float32)
    pool = KVBlockPool(cfg, num_blocks=4, block_size=4)
    assert pool.storage_bytes > 0
    assert ledger.scopes()["kv_pool"] == pool.storage_bytes
    pool.alloc("a", 12)                   # 3 of 4 blocks
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["serve.kv.bytes"]["value"] == 3 * pool.bytes_per_block
    with pytest.raises(Overloaded) as ei:
        pool.alloc("b", 8)
    err = ei.value
    assert err.reason == "kv_exhausted"
    assert err.ledger_breakdown["kv_pool"] == pool.storage_bytes
    assert "HBM ledger" in str(err)


def test_prefix_bytes_are_overlay():
    from mxnet_tpu.models.llama import LlamaConfig
    from mxnet_tpu.serve.kv_cache import KVBlockPool
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=64, dtype=jnp.float32)
    pool = KVBlockPool(cfg, num_blocks=8, block_size=4)
    pool.alloc("a", 8)                    # 2 full blocks
    pool.register_prefix("a", list(range(8)))
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["serve.prefix.bytes"]["value"] == 2 * pool.bytes_per_block
    scopes = ledger.scopes()
    assert scopes["prefix_cache"] == 2 * pool.bytes_per_block
    assert "prefix_cache" in ledger.OVERLAY_SCOPES


def test_draft_pool_uses_own_scope_and_gauge():
    from mxnet_tpu.models.llama import LlamaConfig
    from mxnet_tpu.serve.kv_cache import KVBlockPool
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=64, dtype=jnp.float32)
    pool = KVBlockPool(cfg, num_blocks=4, block_size=4, scope="kv_draft")
    pool.alloc("a", 4)
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["serve.kv.draft_bytes"]["value"] == pool.bytes_per_block
    assert ledger.scopes()["kv_draft"] == pool.storage_bytes


def test_stall_error_report_names_scopes():
    from mxnet_tpu.resilience.errors import StallError
    err = StallError("stalled", site="train.step", deadline_s=5.0,
                     ledger_dump={"kv_pool": 1 << 30, "params": 1 << 20})
    report = err.format_report()
    assert "memory ledger" in report
    assert "kv_pool" in report


# -------------------------------------------------------------- profiling
def test_capture_profile_cpu_fallback(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PROFILE_MIN_S", "0")
    profiling.reset()
    path = telemetry.capture_profile(ms=10, dir=str(tmp_path))
    assert path is not None and os.path.exists(path)
    recs = profiling.records()
    assert recs and recs[-1]["kind"] == "cpu_spans"
    assert telemetry.snapshot()["counters"]["profile.captures"] == 1
    # the capture is announced in the flight ring for the next step record
    telemetry.step_event("train", 1.0)
    events = telemetry.flight_records(limit=1)[0].get("events", [])
    assert any(e.startswith("profile ") for e in events)


def test_capture_profile_rate_limited(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PROFILE_MIN_S", "3600")
    profiling.reset()
    first = telemetry.capture_profile(ms=10, dir=str(tmp_path))
    assert first is not None
    second = telemetry.capture_profile(ms=10, dir=str(tmp_path))
    assert second is None
    assert telemetry.snapshot()["counters"]["profile.rate_limited"] == 1


def test_profile_endpoint_and_429(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PROFILE_MIN_S", "3600")
    monkeypatch.setenv("MXNET_TPU_PROFILE_DIR", str(tmp_path))
    profiling.reset()
    server = export.start_http_server(0)
    base = "http://127.0.0.1:%d" % server.port
    try:
        body = json.loads(urllib.request.urlopen(
            base + "/profile?ms=10", timeout=10).read())
        assert body["ok"] and os.path.exists(body["path"])
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/profile?ms=10", timeout=10)
        assert ei.value.code == 429
        retry = json.loads(ei.value.read())
        assert retry["error"] == "rate_limited"
    finally:
        export.stop_http_server()


def test_disabled_telemetry_is_fully_inert(tmp_path):
    """Under MXNET_TPU_TELEMETRY=0 the whole plane is inert: no gauges,
    no ledger state, no profile capture, no file, no profile directory.
    Subprocess-tested so the gate is evaluated at import like production."""
    code = """
import os, sys, json
import jax, jax.numpy as jnp
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import ledger, profiling
assert not telemetry.ENABLED
ledger.account("params", 4096)
assert ledger.scopes() == {}
assert ledger.reconcile() is None
compiled = jax.jit(lambda x: x + 1).lower(jnp.ones((4,))).compile()
ledger.note_program("p", ledger.harvest(compiled))
assert ledger.programs() == []
out = telemetry.capture_profile(ms=10, dir=sys.argv[1])
assert out is None
assert not os.path.exists(sys.argv[1])
assert profiling.records() == []
snap = telemetry.snapshot()
assert not snap.get("gauges") and not snap.get("counters")
print("INERT_OK")
"""
    env = dict(os.environ, MXNET_TPU_TELEMETRY="0", JAX_PLATFORMS="cpu",
               MXNET_TPU_PROFILE_MIN_S="0")
    env.pop("MXNET_TPU_METRICS_PORT", None)
    r = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "profdir")],
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "INERT_OK" in r.stdout


def test_serve_warmup_programs_have_footprints(tmp_path, monkeypatch):
    """Acceptance: every serve executable (chunked prefill, decode, CoW)
    records a memory_analysis footprint at warmup — on the cold compile
    AND when a second server restores the same programs from the AOT
    cache without recompiling."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.llama import LlamaConfig, llama_init
    monkeypatch.setenv("MXNET_TPU_AOT_CACHE", str(tmp_path))
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, hidden_dim=64, rope_theta=10000.0,
                      max_seq_len=64, dtype=jnp.float32)
    params = llama_init(jax.random.PRNGKey(0), cfg)

    def warm():
        server = mx.serve.InferenceServer(
            params, cfg, max_batch=2, kv_blocks=16, block_size=8,
            max_context=32, queue_cap=8)
        server.warmup()

    warm()
    cold = {p["label"]: p for p in ledger.programs()
            if p["label"].startswith("serve.")}
    assert cold, "no serve.* footprints after cold warmup"
    assert not any(p["cached"] for p in cold.values())

    ledger.reset()
    telemetry.reset()
    warm()
    restored = {p["label"]: p for p in ledger.programs()
                if p["label"].startswith("serve.")}
    assert set(restored) == set(cold)
    assert all(p["cached"] for p in restored.values()), restored
    # visible in the /snapshot payload both times
    assert any(p["label"].startswith("serve.")
               for p in export.snapshot_payload()["memory"]["programs"])


# -------------------------------------------------------------- parse_log
def test_parse_log_mem_mode(tmp_path):
    ledger.account("kv_pool", 4096)
    ledger.note_program("serve.decode", {"temp_bytes": 64, "code_bytes": 0,
                                         "argument_bytes": 128,
                                         "output_bytes": 128, "bytes": 64})
    ledger.reconcile()
    payload = export.snapshot_payload()
    dump = tmp_path / "snap.json"
    dump.write_text(json.dumps(payload))
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "parse_log.py"), str(dump),
         "--mem"], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "kv_pool" in r.stdout
    assert "serve.decode" in r.stdout
    assert "reconcile:" in r.stdout


# -------------------------------------------------------------------- lint
@pytest.mark.lint
def test_new_modules_tracelint_clean_zero_suppressions():
    """ledger/profiling are tracelint-clean with ZERO suppression markers —
    observability code meets the bar it enforces."""
    from mxnet_tpu import analysis
    paths = [
        os.path.join(REPO, "mxnet_tpu", "telemetry", "ledger.py"),
        os.path.join(REPO, "mxnet_tpu", "telemetry", "profiling.py"),
    ]
    findings = analysis.lint_paths(paths)
    assert not findings, "\n".join(f.format() for f in findings)
    for p in paths:
        with open(p) as f:
            assert "tpu-lint:" not in f.read(), \
                "suppression marker in %s" % p
