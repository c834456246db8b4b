"""tools/ CLI tests (im2rec, parse_log, launch covered in test_dist).

reference idiom: the reference ships these as operator-facing tools; tests
drive the CLIs end-to-end on synthetic data.
"""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_dataset(root, classes=2, per_class=3):
    from PIL import Image
    for c in range(classes):
        d = os.path.join(root, "class%d" % c)
        os.makedirs(d)
        for i in range(per_class):
            arr = np.random.randint(0, 255, (10, 12, 3), np.uint8)
            Image.fromarray(arr).save(os.path.join(d, "img%d.jpg" % i))


def test_im2rec_list_and_pack(tmp_path):
    root = tmp_path / "imgs"
    root.mkdir()
    _make_dataset(str(root))
    prefix = str(tmp_path / "data")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "im2rec.py"),
                        prefix, str(root), "--list", "--recursive"],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert os.path.isfile(prefix + ".lst")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "im2rec.py"),
                        prefix, str(root), "--resize", "8"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert os.path.isfile(prefix + ".rec")
    assert os.path.isfile(prefix + ".idx")

    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert len(rec.keys) == 6
    header, payload = recordio.unpack(rec.read_idx(rec.keys[0]))
    assert payload[:2] == b"\xff\xd8"  # JPEG SOI
    assert float(np.asarray(header.label)) in (0.0, 1.0)
    # decodes back through the image module
    from mxnet_tpu import image
    img = image.imdecode(payload, to_ndarray=False)
    assert img.shape[2] == 3 and min(img.shape[:2]) == 8


def test_parse_log(tmp_path):
    log = tmp_path / "train.log"
    log.write_text(
        "INFO Epoch[0] Batch [20] Speed: 100 samples/sec accuracy=0.5\n"
        "INFO Epoch[0] Train-accuracy=0.61\n"
        "INFO Epoch[0] Time cost=12.5\n"
        "INFO Epoch[0] Validation-accuracy=0.58\n"
        "INFO Epoch[1] Train-accuracy=0.75\n"
        "INFO Epoch[1] Time cost=11.0\n"
        "INFO Epoch[1] Validation-accuracy=0.71\n")
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        str(log)], capture_output=True, text=True,
                       timeout=60)
    assert r.returncode == 0, r.stderr
    assert "| 0 | 0.61 | 0.58 | 12.5 |" in r.stdout
    assert "| 1 | 0.75 | 0.71 | 11.0 |" in r.stdout


def test_parse_log_requests(tmp_path):
    """--requests: per-request ttft/queue/prefill/decode/recovery table
    from a /requests dump or a bare request_traces() list (ISSUE 12)."""
    import json
    payload = {
        "rank": 0, "trace_id": "t0",
        "requests": [
            {"request_id": "abc123", "outcome": "completed",
             "wall_ms": 100.0, "accounted_ms": 99.0, "ttft_ms": 40.5,
             "tokens": 8, "requeues": 0,
             "phases_ms": {"queue": 10.0, "prefill": 30.0,
                           "decode": 59.0}},
            {"request_id": "def456", "outcome": "deadline",
             "wall_ms": 50.0, "accounted_ms": 50.0, "tokens": 2,
             "requeues": 1,
             "phases_ms": {"queue": 5.0, "prefill": 20.0, "decode": 15.0,
                           "recovery": 10.0}},
        ],
    }
    dump = tmp_path / "requests.json"
    dump.write_text(json.dumps(payload))
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        "--requests", str(dump), "--format", "csv"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == ("request,outcome,wall_ms,queue_ms,prefill_ms,"
                        "decode_ms,recovery_ms,ttft_ms,tokens,requeues,"
                        "acct_pct")
    assert "abc123,completed,100.0,10.0,30.0,59.0,0.0,40.5,8,0,99.0" \
        in lines
    assert "def456,deadline,50.0,5.0,20.0,15.0,10.0,,2,1,100.0" in lines
    # a bare telemetry.request_traces() list parses the same way
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(payload["requests"]))
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        "--requests", str(bare), "--format", "csv"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "abc123" in r.stdout and "def456" in r.stdout


def test_parse_log_overlap(tmp_path):
    """--overlap: per-step compute/collective/host/idle decomposition +
    overlap fraction from a chrome trace dump (ISSUE 12). The partition
    must sum to the step time exactly."""
    import json
    us = 1e6
    dump = tmp_path / "trace.json"
    dump.write_text(json.dumps({"traceEvents": [
        {"name": "fused_step", "cat": "step", "ph": "X",
         "ts": 0.0, "dur": 1.0 * us, "pid": 0, "tid": 1},
        {"name": "comm.bucket[0..5]", "cat": "comm", "ph": "X",
         "ts": 0.1 * us, "dur": 0.2 * us, "pid": 0, "tid": 1},
        {"name": "checkpoint", "cat": "resilience", "ph": "X",
         "ts": 0.5 * us, "dur": 0.1 * us, "pid": 0, "tid": 1},
        {"name": "x", "cat": "counter", "ph": "C", "ts": 0, "pid": 0},
    ]}))
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        "--overlap", str(dump), "--format", "csv"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    lines = r.stdout.strip().splitlines()
    assert lines[0] == ("step,site,step_ms,compute_ms,collective_ms,"
                        "host_ms,idle_ms,comm_n,overlap_frac")
    row = lines[1].split(",")
    assert row[1] == "fused_step"
    step, comp, coll, host, idle = map(float, (row[2], row[3], row[4],
                                               row[5], row[6]))
    assert (step, coll, host, idle) == (1000.0, 200.0, 100.0, 0.0)
    assert comp + coll + host + idle == step
    # comm phase [0.1, 1.0]: 0.7 of 0.9 s off the collective path
    assert abs(float(row[8]) - 0.7 / 0.9) < 1e-3
    assert lines[-1].startswith("TOTAL,")
    # --site filters step spans by name
    r = subprocess.run([sys.executable,
                        os.path.join(REPO, "tools", "parse_log.py"),
                        "--overlap", "--site", "serve.step", str(dump),
                        "--format", "csv"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    assert "no step spans" in r.stderr


def test_parse_log_kernels(tmp_path):
    """--kernels: Pallas dispatch/fallback table from a telemetry dump
    (ISSUE 10)."""
    import json
    dump = tmp_path / "telemetry.json"
    dump.write_text(json.dumps({
        "counters": {
            "ops.pallas.dispatch": 7,
            "ops.pallas.dispatch.segment_sum": 2,
            "ops.pallas.dispatch.flat_adam": 5,
            "ops.pallas.fallback": 1,
            "ops.pallas.fallback.shape": 1,
            "ops.pallas.fallback.segment_sum.shape": 1,
        },
        "gauges": {"fused_step.pallas_kernels": {"value": 32, "max": 32}},
        "histograms": {"opt.fused_update_ms":
                       {"count": 4, "sum": 8.0, "max": 3.0}},
    }))
    cmd = [sys.executable, os.path.join(REPO, "tools", "parse_log.py"),
           "--kernels", str(dump)]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "| dispatch | flat_adam | 5 |" in r.stdout
    assert "| fallback | segment_sum.shape | 1 |" in r.stdout
    assert "| program | fused_step.pallas_kernels | 32 |" in r.stdout
    assert "| latency | fused_update_ms_avg | 2.0 |" in r.stdout
