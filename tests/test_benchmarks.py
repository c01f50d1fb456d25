"""Benchmark harness smoke: server + client + report round-trip on CPU."""

import json
import subprocess
import sys
import threading
import time
import urllib.request
from pathlib import Path

import pytest
import yaml

# Heavyweight tier: compile-bound or sleep-bound; CI
# runs the slow tier separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmarks" / "ttft_benchmark"


@pytest.fixture(scope="module")
def ttft_server():
    sys.path.insert(0, str(BENCH))
    try:
        import server as ttft_server_mod
    finally:
        sys.path.pop(0)
    engine = ttft_server_mod.Engine("cpu")
    from http.server import ThreadingHTTPServer

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), ttft_server_mod.make_handler(engine))
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield httpd.server_address[1]
    httpd.shutdown()


def test_server_streams_tokens(ttft_server):
    req = urllib.request.Request(
        f"http://127.0.0.1:{ttft_server}/generate",
        data=json.dumps({"prompt_len": 32, "max_tokens": 4}).encode(),
    )
    lines = []
    with urllib.request.urlopen(req, timeout=60) as resp:
        for raw in resp:
            if raw.startswith(b"data: "):
                lines.append(json.loads(raw[6:]))
    assert len(lines) == 4
    assert all("token" in l and "ts" in l for l in lines)
    assert lines[0]["ts"] <= lines[-1]["ts"]


def test_client_and_report_roundtrip(ttft_server, tmp_path):
    url = f"http://127.0.0.1:{ttft_server}"
    base, cand = tmp_path / "base.jsonl", tmp_path / "cand.jsonl"
    for out in (base, cand):
        r = subprocess.run(
            [sys.executable, str(BENCH / "benchmark.py"), "--url", url,
             "--warmup", "1", "--runs", "3", "--prompt-len", "32",
             "--max-tokens", "4", "--out", str(out)],
            capture_output=True, text=True, timeout=300,
        )
        assert r.returncode == 0, r.stderr
        summary = json.loads(r.stdout)
        assert summary["runs"] == 3 and summary["p50_ttft_ms"] > 0

    r = subprocess.run(
        [sys.executable, str(BENCH / "report.py"), "--baseline", str(base),
         "--candidate", str(cand)],
        capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0, r.stderr
    verdict = json.loads(r.stdout)
    assert verdict["metric"] == "p50_ttft_degradation"
    assert "pass" in verdict


def test_deployment_manifests_parse():
    for name in ("job-exclusive.yaml", "job-on-vtpu.yaml"):
        docs = list(yaml.safe_load_all((ROOT / "benchmarks" / "deployments" / name).read_text()))
        assert docs and all(d.get("kind") for d in docs)

