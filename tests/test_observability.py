"""Telemetry v2 — live export, trace correlation, flight recorder, anomaly
detection, and the deadline-aware preemption/commit satellites.

Acceptance (ISSUE 6): a /metrics scrape matches `telemetry.snapshot()`
counter-for-counter; an injected-stall post-mortem embeds the
flight-recorder ring; everything is a no-op under MXNET_TPU_TELEMETRY=0
(no thread, no port). The 2-rank merged-trace test lives in test_dist.py
(slow marker).
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, resilience as rz, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.resilience import faults, watchdog
from mxnet_tpu.resilience.commit import CommitCoordinator
from mxnet_tpu.resilience.errors import PreemptionError, StallError
from mxnet_tpu.resilience.preempt import PreemptionListener, PreemptionNotice
from mxnet_tpu.telemetry import anomaly, export, flight

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    was_enabled = telemetry.ENABLED
    telemetry.enable()
    telemetry.reset()
    yield
    export.stop_http_server()
    export.stop_stream()
    telemetry.reset()
    (telemetry.enable if was_enabled else telemetry.disable)()


def _counter(name):
    return telemetry.snapshot()["counters"].get(name, 0)


def _scrape(port, path="/metrics"):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10) as resp:
        return resp.read().decode("utf-8")


def _seed_metrics():
    telemetry.inc("t.calls", 5)
    telemetry.inc("comm.collectives", 3)
    telemetry.set_gauge("t.mem", 77)
    for v in (0.5, 2.0, 300.0):
        telemetry.observe("t.lat_ms", v)


# ===========================================================================
# prometheus text format
# ===========================================================================
def test_prometheus_text_roundtrip_counters():
    _seed_metrics()
    text = export.prometheus_text()
    parsed = export.parse_prometheus_text(text)
    assert parsed == telemetry.snapshot()["counters"]


def test_prometheus_text_gauges_and_histograms():
    _seed_metrics()
    telemetry.set_gauge("t.mem", 10)          # watermark stays 77
    text = export.prometheus_text(rank=0)
    assert 'mxnet_tpu_t_mem{rank="0"} 10' in text
    assert 'mxnet_tpu_t_mem_max{rank="0"} 77' in text
    # histogram buckets are CUMULATIVE and end at +Inf == count
    lines = [l for l in text.splitlines()
             if l.startswith("mxnet_tpu_t_lat_ms_bucket")]
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines]
    assert counts == sorted(counts)
    assert 'le="+Inf"} 3' in lines[-1]
    assert 'mxnet_tpu_t_lat_ms_count{rank="0"} 3' in text
    assert 'mxnet_tpu_t_lat_ms_sum{rank="0"} 302.5' in text


def test_histogram_quantiles_interpolation():
    """Telemetry-v2 follow-on: p50/p99 derived from the sparse cumulative
    buckets (prometheus histogram_quantile semantics + exact min/max
    clamp) for ALL histograms, not just the rolling step windows."""
    from mxnet_tpu.telemetry.metrics import Histogram
    h = Histogram("t", bounds=(1, 2, 4, 8, 16))
    for v in (0.5, 1.5, 1.7, 3, 3, 3, 5, 6, 7, 12):
        h.observe(v)
    q = export.histogram_quantiles(h.snapshot())
    # p50: rank 5 lands in (2,4] with 3 before it -> 2 + 2*(5-3)/3
    assert abs(q["p50"] - (2 + 2 * (5 - 3) / 3)) < 1e-9
    # p99: interpolation says 15.2 inside (8,16]; exact max clamps to 12
    assert q["p99"] == 12
    # overflow bucket answers with the observed max
    h2 = Histogram("o", bounds=(1,))
    for v in (5.0, 9.0):
        h2.observe(v)
    assert export.histogram_quantiles(h2.snapshot())["p99"] == 9.0
    assert export.histogram_quantiles(Histogram("e").snapshot()) is None
    # the rank-holding bucket's TRUE lower edge holds even when the
    # buckets below it are empty (omitted from the sparse snapshot):
    # 1 obs at 0.5 and 9 at 15.0 -> p50 lives in (8,16], never below 8
    h3 = Histogram("s", bounds=(1, 2, 4, 8, 16))
    h3.observe(0.5)
    for _ in range(9):
        h3.observe(15.0)
    q3 = export.histogram_quantiles(h3.snapshot())
    assert q3["p50"] == pytest.approx(8 + 8 * (5 - 1) / 9)
    assert q3["p50"] >= 8


def test_prometheus_text_emits_quantile_series():
    _seed_metrics()
    text = export.prometheus_text(rank=0)
    assert "# TYPE mxnet_tpu_t_lat_ms_p50 gauge" in text
    p50 = [l for l in text.splitlines()
           if l.startswith('mxnet_tpu_t_lat_ms_p50{rank="0"}')]
    p99 = [l for l in text.splitlines()
           if l.startswith('mxnet_tpu_t_lat_ms_p99{rank="0"}')]
    assert len(p50) == 1 and len(p99) == 1
    snap_h = telemetry.snapshot()["histograms"]["t.lat_ms"]
    q = export.histogram_quantiles(snap_h)
    assert float(p50[0].rsplit(" ", 1)[1]) == pytest.approx(q["p50"])
    assert float(p99[0].rsplit(" ", 1)[1]) == pytest.approx(q["p99"])
    # the quantile gauges must not confuse the counter round-trip
    assert export.parse_prometheus_text(text) == \
        telemetry.snapshot()["counters"]


def test_snapshot_payload_hist_quantiles():
    _seed_metrics()
    payload = export.snapshot_payload()
    assert "t.lat_ms" in payload["hist_quantiles"]
    assert set(payload["hist_quantiles"]["t.lat_ms"]) == {"p50", "p99"}


# ===========================================================================
# live endpoint
# ===========================================================================
@pytest.mark.obs
def test_metrics_endpoint_scrape_parity():
    """ISSUE acceptance: a live /metrics scrape matches telemetry.snapshot()
    counter-for-counter."""
    _seed_metrics()
    server = export.start_http_server(0)      # ephemeral port
    assert server is not None
    parsed = export.parse_prometheus_text(_scrape(server.port))
    assert parsed == telemetry.snapshot()["counters"]
    # scrapes are idempotent reads: a second one still matches
    telemetry.inc("t.calls", 2)
    parsed = export.parse_prometheus_text(_scrape(server.port))
    assert parsed["t.calls"] == 7


@pytest.mark.obs
def test_snapshot_endpoint_payload():
    _seed_metrics()
    telemetry.step_event("fused_step", 5.0)
    server = export.start_http_server(0)
    payload = json.loads(_scrape(server.port, "/snapshot"))
    assert payload["snapshot"] == telemetry.snapshot()
    assert payload["trace_id"] == telemetry.trace_id()
    assert payload["rank"] == 0
    assert payload["step_quantiles"]["fused_step"]["n"] == 1
    assert _scrape(server.port, "/healthz").strip() == "ok"


@pytest.mark.obs
def test_scrape_atomic_under_concurrent_writes():
    """Exporter reads racing inc/observe/set_gauge from step threads must
    see consistent metrics (the concurrency satellite): the gauge
    value/max pair can never be torn (max < value), and counter text
    always parses."""
    server = export.start_http_server(0)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            i += 1
            telemetry.inc("w.calls")
            telemetry.set_gauge("w.gauge", i)
            telemetry.observe("w.lat", i % 100)

    threads = [threading.Thread(target=writer, daemon=True)
               for _ in range(4)]
    for t in threads:
        t.start()
    try:
        for _ in range(20):
            parsed = export.parse_prometheus_text(_scrape(server.port))
            assert parsed.get("w.calls", 0) >= 0
            snap = telemetry.snapshot()
            g = snap["gauges"].get("w.gauge")
            if g is not None:
                assert g["max"] >= g["value"]
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5)


def test_stream_writes_jsonl(tmp_path):
    _seed_metrics()
    path = str(tmp_path / "stream.jsonl")
    streamer = export.start_stream(path, interval_s=0.05)
    assert streamer is not None
    time.sleep(0.2)
    export.stop_stream()
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines, "streamer wrote nothing"
    assert lines[-1]["snapshot"]["counters"]["t.calls"] == 5
    assert lines[-1]["trace_id"] == telemetry.trace_id()


# ===========================================================================
# disabled mode: no thread, no port
# ===========================================================================
def test_disabled_mode_binds_no_port_starts_no_thread():
    """ISSUE acceptance: MXNET_TPU_TELEMETRY=0 + a configured port must
    bind nothing and start no exporter/streamer thread."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = r"""
import os, socket, threading, sys
import mxnet_tpu  # import-time maybe_start_from_env runs here
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import export
assert not telemetry.ENABLED
assert export.start_http_server() is None
assert export.start_stream() is None
names = [t.name for t in threading.enumerate()]
assert not any(n.startswith("mxnet_tpu_metrics") for n in names), names
s = socket.socket()
try:
    s.connect(("127.0.0.1", int(os.environ["MXNET_TPU_METRICS_PORT"])))
except (ConnectionRefusedError, OSError):
    print("PORT_FREE")
finally:
    s.close()
# the flight recorder and anomaly tracker are inert too
telemetry.step_event("fused_step", 5.0)
from mxnet_tpu.telemetry import flight
assert flight.records() == []
# ISSUE 12: the RequestTrace ring and the fleet federation are fully
# inert — NULL traces, empty ring, no network touched even with peers
# configured in the env
from mxnet_tpu.telemetry import federation, request_trace
os.environ["MXNET_TPU_FLEET_PEERS"] = "127.0.0.1:9"
assert federation.fleet_snapshot() is None
assert federation.fleet_metrics_text() is None
tr = request_trace.start("r1")
assert tr is request_trace.NULL_TRACE
tr.mark("queue")
tr.note_drain(RuntimeError("x"))
assert tr.finish("completed") is None
assert request_trace.records() == []
assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                "histograms": {}}
print("DISABLED_OK")
"""
    stream_path = "/tmp/_obs_disabled_stream.jsonl"
    if os.path.exists(stream_path):
        os.remove(stream_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_TELEMETRY="0",
               MXNET_TPU_METRICS_PORT=str(port),
               MXNET_TPU_METRICS_STREAM=stream_path)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PORT_FREE" in r.stdout and "DISABLED_OK" in r.stdout
    assert not os.path.exists(stream_path)


@pytest.mark.obs
def test_env_autostart_binds_configured_port(tmp_path):
    """The inverse: with telemetry ON the env knob starts a real scrapable
    endpoint at import time."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = r"""
import os, urllib.request
import mxnet_tpu
from mxnet_tpu import telemetry
telemetry.inc("autostart.probe", 3)
port = int(os.environ["MXNET_TPU_METRICS_PORT"])
body = urllib.request.urlopen(
    "http://127.0.0.1:%d/metrics" % port, timeout=10).read().decode()
assert "autostart_probe" in body, body
print("AUTOSTART_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_METRICS_PORT=str(port))
    env.pop("MXNET_TPU_TELEMETRY", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "AUTOSTART_OK" in r.stdout


def test_stream_final_flush_on_short_run(tmp_path):
    """A run shorter than one stream interval still leaves a final line:
    the env-autostart path registers an atexit flush."""
    path = str(tmp_path / "short.jsonl")
    code = r"""
import os
import mxnet_tpu
from mxnet_tpu import telemetry
telemetry.inc("short.run", 3)
# exits immediately — well inside the 60 s stream interval
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_METRICS_STREAM=path,
               MXNET_TPU_METRICS_STREAM_S="60")
    env.pop("MXNET_TPU_TELEMETRY", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert lines and lines[-1]["snapshot"]["counters"]["short.run"] == 3


def test_enable_after_disabled_start_brings_up_endpoint():
    """A process started disabled with a configured port gets its endpoint
    when telemetry.enable() runs (the documented runtime switch)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    code = r"""
import os, urllib.request
import mxnet_tpu
from mxnet_tpu import telemetry
assert not telemetry.ENABLED
telemetry.enable()
telemetry.inc("late.enable", 1)
port = int(os.environ["MXNET_TPU_METRICS_PORT"])
body = urllib.request.urlopen(
    "http://127.0.0.1:%d/metrics" % port, timeout=10).read().decode()
assert "late_enable" in body, body
print("LATE_ENABLE_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_TELEMETRY="0",
               MXNET_TPU_METRICS_PORT=str(port),
               MXNET_TPU_METRICS_HOST="127.0.0.1")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LATE_ENABLE_OK" in r.stdout


# ===========================================================================
# trace correlation
# ===========================================================================
def test_trace_id_stable_and_settable():
    tid = telemetry.trace_id()
    assert tid == telemetry.trace_id()
    telemetry.set_trace_id("deadbeef")
    assert telemetry.trace_id() == "deadbeef"


def test_dump_trace_stamps_rank_and_trace_id(tmp_path):
    with telemetry.span("stamped", "test"):
        pass
    path = str(tmp_path / "trace.json")
    telemetry.dump_trace(path)
    obj = json.load(open(path))
    meta = obj["metadata"]
    assert meta["rank"] == 0
    assert meta["trace_id"] == telemetry.trace_id()
    spans = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert spans and all(e["pid"] == 0 for e in spans)


def test_merged_trace_single_process(tmp_path):
    with telemetry.span("local_span", "test"):
        pass
    path = str(tmp_path / "merged.json")
    telemetry.dump_trace(path, merged=True)
    obj = json.load(open(path))
    assert obj["metadata"]["merged"] is True
    assert obj["metadata"]["ranks"] == [0]
    names = {e["name"] for e in obj["traceEvents"] if e["ph"] == "X"}
    assert "local_span" in names


def test_merged_trace_shared_clock(tmp_path):
    """Two fake rank dumps with skewed epochs merge onto one clock: rank
    1's spans shift by the epoch delta, and both ranks get process rows."""
    from mxnet_tpu.telemetry.trace import write_merged_chrome_trace
    dumps = [
        {"rank": 0, "trace_id": "t0", "epoch_unix": 1000.0,
         "events": [["a", "test", 1.0, 0.5, 1]]},
        {"rank": 1, "trace_id": "t0", "epoch_unix": 1002.0,
         "events": [["b", "test", 1.0, 0.5, 1]]},
    ]
    path = str(tmp_path / "m.json")
    write_merged_chrome_trace(path, dumps)
    obj = json.load(open(path))
    spans = {e["name"]: e for e in obj["traceEvents"] if e["ph"] == "X"}
    assert spans["a"]["pid"] == 0 and spans["b"]["pid"] == 1
    # rank 1's epoch started 2 s later: same local ts lands 2e6 µs later
    assert spans["b"]["ts"] - spans["a"]["ts"] == pytest.approx(2e6)
    procs = [e for e in obj["traceEvents"] if e["ph"] == "M"]
    assert {p["pid"] for p in procs} == {0, 1}


def test_merged_trace_tolerates_missing_epoch(tmp_path):
    """An out-of-band dump without an epoch anchor merges unshifted; the
    anchored ranks keep their own base instead of being re-based by a
    unix-epoch-sized offset."""
    from mxnet_tpu.telemetry.trace import write_merged_chrome_trace
    dumps = [
        {"rank": 0, "epoch_unix": 1000.0,
         "events": [["a", "test", 1.0, 0.5, 1]]},
        {"rank": 1,   # pre-v2 dump: no epoch_unix
         "events": [["b", "test", 1.0, 0.5, 1]]},
    ]
    path = str(tmp_path / "m.json")
    write_merged_chrome_trace(path, dumps)
    spans = {e["name"]: e for e in
             json.load(open(path))["traceEvents"] if e["ph"] == "X"}
    assert spans["a"]["ts"] == pytest.approx(1e6)  # NOT shifted by ~1000 s
    assert spans["b"]["ts"] == pytest.approx(1e6)


def test_mxtop_stream_tail_read(tmp_path):
    """fetch_stream reads only the tail of a large stream file and skips a
    partially-appended last line."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import mxtop
    finally:
        sys.path.pop(0)
    path = str(tmp_path / "big.jsonl")
    with open(path, "w") as f:
        for i in range(5000):
            f.write(json.dumps({"ts": i, "snapshot": {}}) + "\n")
        f.write('{"ts": 9999, "snapsho')      # torn mid-append line
    assert mxtop.fetch_stream(path, block=256)["ts"] == 4999
    with open(str(tmp_path / "empty.jsonl"), "w"):
        pass
    with pytest.raises(ValueError):
        mxtop.fetch_stream(str(tmp_path / "empty.jsonl"))


def test_aggregate_trace_local():
    with telemetry.span("agg_span", "test"):
        pass
    dumps = telemetry.aggregate_trace()
    assert len(dumps) == 1
    assert dumps[0]["rank"] == 0
    assert any(e[0] == "agg_span" for e in dumps[0]["events"])


# ===========================================================================
# comm-overlap attribution (ISSUE 12 tentpole)
# ===========================================================================
def test_attribution_partition_sums_to_step():
    """The compute/collective/host/idle decomposition is a partition of
    the step window — it sums to step time exactly (the acceptance's 5%
    bound holds by construction), with overlapping comm spans unioned and
    host spans deduplicated against comm."""
    from mxnet_tpu.telemetry import attribution
    events = [
        ("fused_step", "step", 0.0, 1.0, 1),
        ("comm.bucket[a]", "comm", 0.1, 0.2, 1),     # [0.10, 0.30]
        ("comm.bucket[b]", "comm", 0.25, 0.1, 1),    # [0.25, 0.35] overlap
        ("checkpoint", "resilience", 0.5, 0.1, 1),   # host
        ("checkpoint", "resilience", 0.3, 0.1, 1),   # half under comm
    ]
    row = attribution.attribute_window(events, 0.0, 1.0)
    assert row["collective_ms"] == pytest.approx(250.0)   # union, not sum
    assert row["comm_busy_ms"] == pytest.approx(300.0)
    assert row["host_ms"] == pytest.approx(150.0)         # comm part cut
    assert row["idle_ms"] == 0.0
    assert row["compute_ms"] == pytest.approx(1000 - 250 - 150)
    total = (row["compute_ms"] + row["collective_ms"] + row["host_ms"]
             + row["idle_ms"])
    assert total == pytest.approx(row["step_ms"])
    assert row["comm_launches"] == 2
    # overlap: comm phase = [0.1, 1.0]; host was off the comm path for
    # 0.9 - 0.25 of it
    assert row["overlap_frac"] == pytest.approx((0.9 - 0.25) / 0.9,
                                                abs=1e-3)


def test_overlap_report_on_live_spans_and_gauges():
    """overlap_report() reads the live span buffer; step_event publishes
    the same decomposition as attrib.* gauges and a flight record."""
    # a step that JUST ended: step_event's live window is [now-dur, now],
    # exactly how the real step sites call it
    ts = telemetry.span_clock() - 0.02
    telemetry.record_span("comm.bucket[0..5]", "comm", ts + 0.001, 0.004)
    telemetry.record_span("train_step", "step", ts, 0.02)
    rep = telemetry.overlap_report(site="train_step")
    assert rep["summary"]["steps"] == 1
    row = rep["steps"][0]
    assert row["collective_ms"] == pytest.approx(4.0, rel=0.01)
    assert row["comm_launches"] == 1
    assert 0.0 < row["overlap_frac"] < 1.0
    # the live per-step pass: gauges + flight "attrib" record
    telemetry.step_event("train_step", 20.0)
    gauges = telemetry.snapshot()["gauges"]
    assert "attrib.train_step.collective_ms" in gauges
    rec = telemetry.flight_records()[-1]
    assert "attrib" in rec and rec["attrib"]["comm_launches"] >= 1


def test_overlap_report_no_comm_steps():
    ts = telemetry.span_clock()
    telemetry.record_span("fused_step", "step", ts, 0.01)
    rep = telemetry.overlap_report(site="fused_step")
    row = rep["steps"][0]
    assert row["overlap_frac"] is None
    assert row["compute_ms"] == pytest.approx(row["step_ms"])
    assert rep["summary"]["overlap_frac"] is None


# ===========================================================================
# /requests endpoint + fleet federation (ISSUE 12 tentpole)
# ===========================================================================
@pytest.mark.obs
def test_requests_endpoint_serves_trace_ring():
    from mxnet_tpu.telemetry import request_trace
    tr = request_trace.start("req-endpoint-1")
    tr.mark("queue").mark("prefill")
    tr.finish("completed", tokens=3)
    server = export.start_http_server(0)
    payload = json.loads(_scrape(server.port, "/requests"))
    assert payload["rank"] == 0
    assert payload["trace_id"] == telemetry.trace_id()
    reqs = {r["request_id"]: r for r in payload["requests"]}
    assert reqs["req-endpoint-1"]["outcome"] == "completed"
    assert reqs["req-endpoint-1"]["tokens"] == 3


@pytest.mark.obs
def test_fleet_endpoints_local_only():
    """With no peers configured the fleet view degrades to this rank —
    same payload shape, workers=1 — so dashboards need no special case."""
    _seed_metrics()
    server = export.start_http_server(0)
    fleet = json.loads(_scrape(server.port, "/fleet/snapshot"))
    assert fleet["workers"] == 1
    assert fleet["stale_ranks"] == [] and fleet["missing"] == []
    assert fleet["merged"]["counters"]["t.calls"] == 5
    assert set(fleet["ranks"]) == {"0"}
    text = _scrape(server.port, "/fleet/metrics")
    assert 'mxnet_tpu_t_calls{rank="0"} 5' in text
    assert "mxnet_tpu_fleet_workers 1" in text


@pytest.mark.obs
def test_fleet_snapshot_merges_peer_and_tolerates_death():
    """A (stub) peer's /snapshot merges into the fleet view rank-labeled;
    when the peer dies its last good payload is served stale-marked and
    telemetry.federation.stale_ranks counts it."""
    import http.server
    from mxnet_tpu.telemetry import federation
    peer_payload = {
        "rank": 1, "trace_id": "t", "hist_quantiles": {},
        "snapshot": {"counters": {"t.calls": 7, "peer.only": 2},
                     "gauges": {}, "histograms": {}},
    }

    class _Peer(http.server.BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - stdlib contract
            body = json.dumps(peer_payload).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # noqa: A002
            pass

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Peer)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    _seed_metrics()
    federation.configure(["127.0.0.1:%d" % httpd.server_address[1]])
    try:
        fleet = federation.fleet_snapshot()
        assert fleet["workers"] == 2
        assert set(fleet["ranks"]) == {"0", "1"}
        assert fleet["merged"]["counters"]["t.calls"] == 12   # 5 + 7
        assert fleet["merged"]["counters"]["peer.only"] == 2
        assert fleet["stale_ranks"] == []
        text = federation.fleet_metrics_text()
        assert 'mxnet_tpu_t_calls{rank="0"} 5' in text
        assert 'mxnet_tpu_t_calls{rank="1"} 7' in text
        # one HELP/TYPE header despite two ranks: the blob stays parseable
        assert text.count("# TYPE mxnet_tpu_t_calls counter") == 1
        # kill the peer: stale cache serves, stale_ranks counts
        httpd.shutdown()
        httpd.server_close()
        fleet = federation.fleet_snapshot()
        assert len(fleet["stale_ranks"]) == 1
        assert fleet["ranks"]["1"]["stale"] is True
        assert fleet["workers"] == 2                          # still both
        assert fleet["merged"]["counters"][
            "telemetry.federation.stale_ranks"] == 1
    finally:
        federation.reset()
        try:
            httpd.server_close()
        except OSError:
            pass


def test_fleet_missing_peer_without_cache(monkeypatch):
    """A peer that NEVER answered is reported missing (not fabricated),
    and each failed scrape ticks the stale counter."""
    from mxnet_tpu.telemetry import federation
    monkeypatch.setenv("MXNET_TPU_FLEET_TIMEOUT_S", "0.2")
    federation.configure(["127.0.0.1:9"])       # nothing listens there
    try:
        fleet = federation.fleet_snapshot()
        assert fleet["missing"] == ["http://127.0.0.1:9"]
        assert fleet["workers"] == 1
        assert fleet["merged"]["counters"][
            "telemetry.federation.stale_ranks"] == 1
        fleet = federation.fleet_snapshot()
        assert fleet["merged"]["counters"][
            "telemetry.federation.stale_ranks"] == 2
    finally:
        federation.reset()


def test_fleet_peers_env_parsing(monkeypatch):
    from mxnet_tpu.telemetry import federation
    monkeypatch.setenv("MXNET_TPU_FLEET_PEERS",
                       "10.0.0.2:9100, http://10.0.0.3:9100/,")
    assert federation.peers() == ["http://10.0.0.2:9100",
                                  "http://10.0.0.3:9100"]
    federation.configure(["a:1"])
    assert federation.peers() == ["http://a:1"]
    federation.reset()
    assert federation.peers() == ["http://10.0.0.2:9100",
                                  "http://10.0.0.3:9100"]


# ===========================================================================
# flight recorder
# ===========================================================================
def test_flight_record_deltas_and_ring_bound():
    rec = flight.FlightRecorder(maxlen=4)
    telemetry.inc("comm.collectives", 2)
    rec.record_step("fused_step", 10.0)
    telemetry.inc("comm.collectives", 3)
    r = rec.record_step("fused_step", 11.0)
    assert r["deltas"]["comm.collectives"] == 3
    for i in range(10):
        rec.record_step("fused_step", float(i))
    recs = rec.records()
    assert len(recs) == 4                      # bounded ring
    assert recs[-1]["seq"] == 12


def test_flight_buffers_events_and_retrace_reasons():
    flight.note_event("checkpoint", "step=3")
    flight.note_retrace("FusedTrainStep", "arg0 shape (2,3)->(4,3)")
    telemetry.step_event("fused_step", 5.0)
    rec = telemetry.flight_records()[-1]
    assert rec["events"] == ["checkpoint step=3"]
    assert "arg0 shape" in rec["retrace_reasons"][0]
    # buffers drain into ONE record
    telemetry.step_event("fused_step", 5.0)
    rec2 = telemetry.flight_records()[-1]
    assert "events" not in rec2 and "retrace_reasons" not in rec2


def test_flight_dump_roundtrip(tmp_path):
    telemetry.step_event("trainer", 7.0)
    path = flight.dump(str(tmp_path / "flight.json"), reason="test")
    obj = json.load(open(path))
    assert obj["reason"] == "test"
    assert obj["trace_id"] == telemetry.trace_id()
    assert obj["records"][-1]["site"] == "trainer"
    assert flight.dump(str(tmp_path / "nope.json")) is not None
    flight.reset()
    assert flight.dump(str(tmp_path / "empty.json")) is None


def test_stall_post_mortem_embeds_flight_ring():
    """ISSUE acceptance: an injected hang's StallError carries the flight
    ring and format_report() renders it."""
    telemetry.step_event("fused_step", 12.0)
    telemetry.step_event("fused_step", 13.0)
    with pytest.raises(StallError) as ei:
        with faults.inject("obs.site:hang:1:30"):
            with watchdog.guard("obs.site", deadline_s=0.25):
                faults.check("obs.site")
    err = ei.value
    assert err.flight_dump, "StallError must embed the flight ring"
    assert err.flight_dump[-1]["site"] == "fused_step"
    report = err.format_report()
    assert "flight recorder" in report
    assert "fused_step" in report


def test_runner_stall_flight_ledger(tmp_path):
    """End-to-end: a fused-step run that hangs produces a StallError whose
    flight ring shows the steps that led up to it, and the recovered run's
    ledger carries the restore event.

    The deadline is 5 s and not the 0.5 s it was: the first guarded step
    compiles the program (0.46 s on an idle machine, 0.15 s again after
    the restore), so six workers' load made a second stall and two
    restarts of one. A load that stretches that compile tenfold stretches
    the whole suite past its time limit first; the test waits the
    deadline out once, inside the injected hang."""
    mx.random.seed(42)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    fused = gluon.FusedTrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), tr)
    rng = np.random.RandomState(0)
    X = rng.rand(4, 8, 6).astype(np.float32)
    Y = rng.randint(0, 3, (4, 8)).astype(np.float32)
    batch_fn = lambda i: (nd.array(X[i]), nd.array(Y[i]))  # noqa: E731
    with faults.inject("train.step:hang:3:30"):
        runner = rz.ResilientRunner.for_fused_step(
            fused, batch_fn, ckpt_dir=str(tmp_path / "ck"), ckpt_every=1,
            max_restarts=2, step_deadline_s=5.0)
        report = runner.run(4)
    assert report.restarts == 1
    events = [e for r in telemetry.flight_records()
              for e in r.get("events", [])]
    assert any(e.startswith("restore") for e in events), events
    assert any(e.startswith("checkpoint") for e in events), events


def test_flight_crash_dump_on_unhandled_exception(tmp_path):
    """The excepthook chain dumps the ring when the process dies on an
    unhandled exception."""
    code = r"""
import mxnet_tpu
from mxnet_tpu import telemetry
telemetry.step_event("fused_step", 9.0)
raise RuntimeError("synthetic crash for the flight recorder")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_FLIGHT_DIR=str(tmp_path))
    env.pop("MXNET_TPU_TELEMETRY", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=180)
    assert r.returncode != 0
    assert "flight recorder dumped to" in r.stderr, r.stderr
    dumps = [f for f in os.listdir(str(tmp_path))
             if f.startswith("flight_rank0_")]
    assert len(dumps) == 1
    obj = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert "synthetic crash" in obj["reason"]
    assert obj["records"][-1]["site"] == "fused_step"


def test_runner_dumps_flight_on_fatal(tmp_path, monkeypatch):
    """A run dying on an exhausted restart budget leaves a flight dump."""
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path))
    calls = {"n": 0}

    def step_fn(i):
        calls["n"] += 1
        telemetry.step_event("train_step", 1.0)
        raise PreemptionError("host keeps dying")

    runner = rz.ResilientRunner(
        step_fn, state_get=lambda: {"x": 1}, state_set=lambda t: None,
        ckpt_dir=str(tmp_path / "ck"), ckpt_every=1, max_restarts=1)
    with pytest.raises(PreemptionError):
        runner.run(3)
    dumps = [f for f in os.listdir(str(tmp_path))
             if f.startswith("flight_rank0_")]
    assert len(dumps) == 1
    obj = json.load(open(os.path.join(str(tmp_path), dumps[0])))
    assert "PreemptionError" in obj["reason"]


# ===========================================================================
# anomaly detection
# ===========================================================================
def test_anomaly_counter_fires_on_step_time_regression():
    """ISSUE satellite: a synthetic step-time regression trips the rolling-
    median detector — counter + per-site counter + marker span."""
    for _ in range(12):
        telemetry.step_event("fused_step", 10.0)
    assert _counter("telemetry.anomaly.step_time") == 0
    telemetry.step_event("fused_step", 500.0)   # 50× the median
    assert _counter("telemetry.anomaly.step_time") == 1
    assert _counter("telemetry.anomaly.step_time.fused_step") == 1
    names = [e[0] for e in telemetry.span_events()]
    assert "anomaly@fused_step" in names
    rec = telemetry.flight_records()[-1]
    assert rec["anomalies"] == ["step_time"]


def test_anomaly_quiet_on_steady_cadence_and_warmup():
    tracker = anomaly.StepTimeTracker(factor=4.0)
    # the first WARMUP steps never fire, even when wildly different
    assert tracker.observe("s", 1.0) == []
    assert tracker.observe("s", 1000.0) == []
    t2 = anomaly.StepTimeTracker(factor=4.0)
    for _ in range(20):
        assert t2.observe("s", 10.0) == []
    assert t2.observe("s", 20.0) == []          # 2× median: fine


def test_anomaly_slo_tracking(monkeypatch):
    tracker = anomaly.StepTimeTracker(slo_ms=50.0)
    assert [k for k, _ in tracker.observe("s", 60.0)] == ["slo"]
    assert tracker.observe("s", 10.0) == []
    monkeypatch.setenv("MXNET_TPU_STEP_SLO_MS", "25")
    anomaly.reset()
    telemetry.step_event("train_step", 30.0)
    assert _counter("telemetry.anomaly.slo") == 1
    assert _counter("telemetry.anomaly.slo.train_step") == 1


def test_step_quantiles():
    for ms in range(1, 101):
        telemetry.step_event("trainer", float(ms))
    q = telemetry.step_quantiles("trainer")
    # window 64: the last 64 observations are 37..100
    assert q["n"] == 64
    assert 60 <= q["p50"] <= 75
    assert q["p99"] >= 99
    assert telemetry.step_quantiles()["trainer"] == q
    assert telemetry.step_quantiles("unseen") is None


# ===========================================================================
# resilience satellites
# ===========================================================================
def test_ckpt_save_ms_histogram_recorded(tmp_path):
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: {"x": 1},
        state_set=lambda t: None, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=1)
    runner.run(3)
    h = telemetry.snapshot()["histograms"]["ckpt.save_ms"]
    assert h["count"] == 3
    assert h["max"] > 0


def test_preempt_skips_save_when_window_too_short(tmp_path):
    """SIGTERM deadline awareness: with the rolling max save time bigger
    than the remaining grace window, the proactive save is skipped and
    recovery falls back to restore-and-replay."""
    # seed the save-cost ledger with a pathologically slow save
    telemetry.observe("ckpt.save_ms", 60000.0)
    listener = PreemptionListener(poll_fn=False, sigterm=False,
                                  grace_s=0.5)
    listener.notify("maintenance imminent", "poll")
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: {"x": 1},
        state_set=lambda t: None, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=100, preempt_listener=listener)
    saves0 = _counter("resilience.proactive_checkpoints")
    with pytest.raises(PreemptionError) as ei:
        runner._check_preempt(5, rz.RunReport())
    assert "skipped" in str(ei.value)
    assert _counter("resilience.preempt.save_skipped") == 1
    assert _counter("resilience.proactive_checkpoints") == saves0


def test_preempt_saves_when_window_fits(tmp_path):
    telemetry.observe("ckpt.save_ms", 5.0)      # fast saves
    listener = PreemptionListener(poll_fn=False, sigterm=False,
                                  grace_s=30.0)
    listener.notify("maintenance imminent", "poll")
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: {"x": 1},
        state_set=lambda t: None, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=100, preempt_listener=listener)
    report = rz.RunReport()
    with pytest.raises(PreemptionError) as ei:
        runner._check_preempt(5, report)
    assert "committed" in str(ei.value)
    assert report.proactive_ckpts == 1
    assert _counter("resilience.preempt.save_skipped") == 0


def test_notice_deadline_and_grace_env(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PREEMPT_GRACE_S", "7")
    n = PreemptionNotice("r", "sigterm")
    assert n.deadline - n.received_at == pytest.approx(7.0)
    assert 6.0 < n.remaining_s() <= 7.0
    n2 = PreemptionNotice("r", "poll", grace_s=0.0)
    assert n2.remaining_s() <= 0.0


class _FakeCoordClient:
    def __init__(self):
        self.kv = {}
        self.deleted = []

    def key_value_set(self, key, value):
        self.kv[key] = value

    def wait_at_barrier(self, key, timeout_ms):
        pass

    def blocking_key_value_get(self, key, timeout_ms):
        return self.kv[key]

    def key_value_delete(self, key):
        self.deleted.append(key)
        self.kv.pop(key, None)


def test_commit_cleanup_round_bounds_kv_growth():
    """ROADMAP carry-over: every KV election reclaims the previous round's
    key, so coordinator-KV growth is bounded over long runs."""
    from mxnet_tpu.resilience import commit as commit_mod
    coord = CommitCoordinator()
    client = _FakeCoordClient()
    rounds = [commit_mod._next_round() for _ in range(4)]
    steps = coord._exchange_kv(client, 10, "save", rounds[0])
    assert steps == [10]
    assert client.deleted == []                # nothing to reclaim yet
    coord._exchange_kv(client, 11, "save", rounds[1])
    coord._exchange_kv(client, 11, "restore", rounds[2])
    coord._exchange_kv(client, 12, "save", rounds[3])
    assert len(client.deleted) == 3
    # mixed kinds reclaim the right namespaces, in order
    assert "save/round_%d" % rounds[0] in client.deleted[0]
    assert "save/round_%d" % rounds[1] in client.deleted[1]
    assert "restore/round_%d" % rounds[2] in client.deleted[2]
    # steady state: exactly ONE live key per rank
    assert len(client.kv) == 1
    assert _counter("resilience.commit.cleanups") == 3


def test_commit_cleanup_reclaims_failed_rounds():
    """A round whose barrier dies still gets its key reclaimed by the next
    successful election (flaky coordinators must not leak a key per
    failure)."""
    from mxnet_tpu.resilience import commit as commit_mod

    class FlakyBarrier(_FakeCoordClient):
        def __init__(self):
            super().__init__()
            self.fail_next = False

        def wait_at_barrier(self, key, timeout_ms):
            if self.fail_next:
                self.fail_next = False
                raise TimeoutError("barrier timed out")

    coord = CommitCoordinator()
    client = FlakyBarrier()
    client.fail_next = True
    with pytest.raises(TimeoutError):
        coord._exchange_kv(client, 3, "save", commit_mod._next_round())
    assert len(client.kv) == 1                 # the failed round's key
    coord._exchange_kv(client, 4, "save", commit_mod._next_round())
    assert len(client.kv) == 1                 # failed round reclaimed
    assert len(client.deleted) == 1


def test_preempt_skips_save_when_grace_already_expired(tmp_path):
    """Even with NO save history, an expired grace window skips the save
    (starting a save with zero budget guarantees the torn write)."""
    listener = PreemptionListener(poll_fn=False, sigterm=False,
                                  grace_s=0.0)
    listener.notify("too late", "poll")
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: {"x": 1},
        state_set=lambda t: None, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=100, preempt_listener=listener)
    report = rz.RunReport()
    with pytest.raises(PreemptionError):
        runner._check_preempt(0, report)
    assert _counter("resilience.preempt.save_skipped") == 1
    assert report.proactive_ckpts == 0


def test_worst_save_ms_is_rolling_not_lifetime(tmp_path):
    """One cold outlier save must age out of the budgeting window once
    later saves are fast (a lifetime max would disable proactive
    checkpoints forever)."""
    runner = rz.ResilientRunner(
        lambda i: 0.0, state_get=lambda: {"x": 1},
        state_set=lambda t: None, ckpt_dir=str(tmp_path / "ck"),
        ckpt_every=1)
    runner._save_ms_window.append(60000.0)     # the cold outlier
    assert runner._worst_save_ms() == 60000.0
    for _ in range(8):                          # window maxlen
        runner._save_ms_window.append(5.0)
    assert runner._worst_save_ms() == 5.0
    # before this runner's first save, the histogram max is the prior
    runner._save_ms_window.clear()
    telemetry.observe("ckpt.save_ms", 123.0)
    assert runner._worst_save_ms() == 123.0


def test_commit_cleanup_survives_missing_delete_support():
    from mxnet_tpu.resilience import commit as commit_mod

    class NoDelete(_FakeCoordClient):
        def key_value_delete(self, key):
            raise RuntimeError("UNIMPLEMENTED")

    coord = CommitCoordinator()
    client = NoDelete()
    coord._exchange_kv(client, 1, "save", commit_mod._next_round())
    steps = coord._exchange_kv(client, 2, "save", commit_mod._next_round())
    assert steps == [2]                        # election unharmed
    assert _counter("resilience.commit.cleanups") == 0


# ===========================================================================
# tooling: parse_log modes + mxtop
# ===========================================================================
def test_parse_log_flight_mode(tmp_path):
    telemetry.inc("comm.collectives", 4)
    telemetry.step_event("fused_step", 10.0)
    dump = flight.dump(str(tmp_path / "flight.json"), reason="test")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--flight", "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "step,site,step_ms,anomalies,compiles,events,notes"
    assert any("fused_step" in l and "coll=4" in l for l in lines[1:])


def test_parse_log_anomalies_mode(tmp_path):
    # the real step paths observe the histogram AND fire step_event
    for ms in [10.0] * 12 + [999.0]:
        telemetry.observe("trainer.step_ms", ms)
        telemetry.step_event("trainer", ms)
    dump = str(tmp_path / "telemetry.json")
    telemetry.dump(dump)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--anomalies", "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "step_time,count,1" in r.stdout
    assert "step_time.trainer,count,1" in r.stdout
    assert "trainer.step_ms,max_ms,999" in r.stdout


def test_parse_log_serve_mode(tmp_path):
    """`parse_log.py --serve`: tokens/s, ttft/tpot quantiles, pressure
    gauges, and shed counts from a telemetry dump (ISSUE 8 CI satellite)."""
    telemetry.inc("serve.requests", 10)
    telemetry.inc("serve.completed", 8)
    telemetry.inc("serve.tokens", 64)
    telemetry.inc("serve.shed", 2)
    telemetry.inc("serve.shed.queue_full", 2)
    telemetry.set_gauge("serve.tokens_per_s", 123.4)
    telemetry.set_gauge("serve.queue_depth", 0)
    telemetry.set_gauge("serve.queue_depth", 3)
    telemetry.set_gauge("serve.queue_depth", 0)
    for ms in (5.0, 6.0, 50.0):
        telemetry.observe("serve.ttft_ms", ms)
        telemetry.observe("serve.tpot_ms", ms / 10)
    dump = str(tmp_path / "serve.json")
    telemetry.dump(dump)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
         dump, "--serve", "--format", "csv"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == "metric,value"
    rows = dict(l.rsplit(",", 1) for l in lines[1:])
    assert rows["tokens_per_s"] == "123.4"
    assert rows["requests"] == "10"
    assert rows["shed"] == "2" and rows["shed.queue_full"] == "2"
    assert rows["queue_depth_peak"] == "3"
    assert float(rows["ttft_ms_p50"]) > 0
    assert float(rows["tpot_ms_p99"]) > 0


def test_mxtop_once_from_stream(tmp_path):
    telemetry.inc("comm.collectives", 9)
    telemetry.set_gauge("memory.cpu0.bytes_in_use", 4096)
    telemetry.step_event("fused_step", 3.0)
    path = str(tmp_path / "stream.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps(export.snapshot_payload()) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtop.py"),
         "--stream", path, "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "mxtop" in r.stdout
    assert "fused_step" in r.stdout
    assert "collectives" in r.stdout
    assert "cpu0" in r.stdout


@pytest.mark.obs
def test_mxtop_once_from_endpoint():
    telemetry.step_event("trainer", 2.0)
    server = export.start_http_server(0)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtop.py"),
         "--port", str(server.port), "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "trainer" in r.stdout


def test_mxtop_serve_view_single_and_fleet(tmp_path):
    """`mxtop --serve` renders tokens/s, queue/batch pressure, shed
    counts and TTFT/TPOT quantiles from a single /snapshot payload AND
    from a /fleet/snapshot payload (one row per rank + fleet totals)."""
    from mxnet_tpu.telemetry import federation
    telemetry.inc("serve.requests", 10)
    telemetry.inc("serve.completed", 8)
    telemetry.inc("serve.shed", 2)
    telemetry.inc("serve.shed.queue_full", 2)
    telemetry.set_gauge("serve.tokens_per_s", 123.4)
    telemetry.set_gauge("serve.queue_depth", 3)
    telemetry.set_gauge("serve.batch_occupancy", 4)
    for ms in (5.0, 6.0, 50.0):
        telemetry.observe("serve.ttft_ms", ms)
        telemetry.observe("serve.tpot_ms", ms / 10)
    single = str(tmp_path / "single.jsonl")
    with open(single, "w") as f:
        f.write(json.dumps(export.snapshot_payload()) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtop.py"),
         "--stream", single, "--serve", "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "123.40" in r.stdout          # tokens/s
    assert "queue_full=2" in r.stdout    # shed by reason
    assert "ttft p50/p99" in r.stdout
    fleet = str(tmp_path / "fleet.jsonl")
    with open(fleet, "w") as f:
        f.write(json.dumps(federation.fleet_snapshot()) + "\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtop.py"),
         "--stream", fleet, "--serve", "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "fleet: 1 rank(s)" in r.stdout
    assert any(line.startswith("  fleet ")      # fleet totals row present
               for line in r.stdout.splitlines())


def test_mxtop_once_fails_cleanly_without_target(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "mxtop.py"),
         "--stream", str(tmp_path / "missing.jsonl"), "--once"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode != 0
    assert "cannot read" in r.stderr


# ===========================================================================
# tracelint: the new threaded modules stay TPU006-clean, no suppressions
# ===========================================================================
@pytest.mark.lint
def test_new_observability_modules_tpu006_clean():
    from mxnet_tpu import analysis
    paths = [os.path.join(REPO, "mxnet_tpu", "telemetry", m)
             for m in ("export.py", "flight.py", "anomaly.py",
                       "federation.py", "request_trace.py",
                       "attribution.py")]
    findings = [f for p in paths
                for f in analysis.lint_file(p, rules=["TPU006"])]
    assert not findings, "\n".join(f.format() for f in findings)
    for p in paths:
        src = open(p).read()
        assert "tpu-lint: disable" not in src, \
            "%s must stay clean WITHOUT suppressions" % p
