"""chip_smoke.py's check functions at toy size on the CPU, and the ways a
dead engine must FAIL them (ISSUE 21 satellites b and c).

The chip run itself needs a TPU; here the same functions run the same
control flow on a vocab-512 model with Pallas interpreted, so a change that
breaks the smoke's own logic is caught before chip time is spent. Nothing
here is a device number.
"""

import dataclasses
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def toy(smoke):
    """The ttft server's own ``cpu`` preset, started once."""
    server = smoke.ttft_server.Engine("cpu")
    yield server
    server.engine.stop()


WAIT = 120.0


def _paged(cfg, **over):
    from vtpu.serving import ServingConfig

    return ServingConfig(**{**dict(
        slots=2, prefill_buckets=(32,), prefill_batch_sizes=(1,),
        max_new_tokens=8, kv_page=8, kv_swap=8, prefill_chunk=16), **over})


def test_script_refuses_cpu():
    """``python chip_smoke.py`` without a TPU exits non-zero before any
    model is built, and prints no result line."""
    r = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "platform=cpu" in r.stdout
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_server_check_toy(smoke, toy):
    info = smoke.check_server(
        toy, [(20, 6), (100, 8), (32, 4), (128, 6), (60, 5)],
        in_flight=2, wait_s=WAIT)
    assert info["requests"] == 5 and info["tokens"] == 29
    assert info["ttft_ms_p50"] > 0


def test_paged_and_routes_toy(smoke, toy):
    """Forced kernel route (auto never picks it off-TPU): the check sees
    kernel ticks and a gather-free decode step; with no marker to look for
    it cannot claim a compiled kernel."""
    cfg = dataclasses.replace(toy.cfg, max_seq=64)
    for pcfg in (cfg, dataclasses.replace(cfg, kv_int8=True)):
        info = smoke.check_paged(
            toy.params, pcfg, _paged(pcfg, paged_attn="kernel"),
            [(40, 6), (20, 8)], kernel_bucket=64, kernel_marker=None,
            wait_s=WAIT)
        assert info["kernel_ticks"] > 0 and info["prefill_chunks"] > 0
        routes = smoke.check_trunk_routes(
            toy.params, pcfg, page=8, window=64, slots=2, free_steps=4,
            timed_steps=2, reps=1)
        assert routes["logit_rel_diff"] <= smoke.ROUTE_LOGIT_RTOL


def test_paged_check_rejects_a_quiet_gather(smoke, toy):
    """An engine that served every token on the gather route fails the
    check: correct streams are not enough."""
    cfg = dataclasses.replace(toy.cfg, max_seq=64)
    with pytest.raises(smoke.SmokeFailure, match="kernel route"):
        smoke.check_paged(
            toy.params, cfg, _paged(cfg), [(40, 6)], kernel_bucket=64,
            kernel_marker=None, wait_s=WAIT)
    with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
        smoke.check_paged(
            toy.params, cfg, _paged(cfg, paged_attn="kernel"), [(40, 6)],
            kernel_bucket=64, kernel_marker="tpu_custom_call", wait_s=WAIT)


def test_device_loop_and_fused_spec_toy(smoke, toy):
    cfg = dataclasses.replace(toy.cfg, max_seq=64)
    loop = _paged(cfg, kv_swap=None, prefill_chunk=None, decode_loop_k=4,
                  max_new_tokens=12)
    info = smoke.check_device_loop(toy.params, cfg, loop, prompt_len=24,
                                   budget=12, wait_s=WAIT)
    assert info["loop_flushes"] > 0 and info["spec_ticks"] == 0
    info = smoke.check_device_loop(
        toy.params, cfg, dataclasses.replace(loop, spec_tokens=3),
        prompt_len=24, budget=12, wait_s=WAIT)
    assert info["loop_flushes"] > 0


def test_multichip_checks_toy(smoke, toy):
    """The four-chip part on four virtual CPU devices: a tp=4 engine
    (one head a device) and four pinned replicas with one migration."""
    from jax.sharding import Mesh

    devices = jax.devices()[:4]
    if len(devices) < 4:
        pytest.skip("needs 4 devices")
    cfg = dataclasses.replace(toy.cfg, max_seq=64)
    info = smoke.check_paged(
        toy.params, cfg, _paged(cfg, paged_attn="kernel"),
        [(40, 6), (20, 8)], kernel_bucket=64, kernel_marker=None,
        wait_s=WAIT, mesh=Mesh(np.array(devices), ("tp",)))
    assert info["kernel_ticks"] > 0
    info = smoke.check_pinned_replicas(
        toy.params, cfg, _paged(cfg), devices, prompt_len=10, budget=8,
        wait_s=WAIT)
    assert info["migration"] in ("resident", "host", "recompute")
    assert len({r["device"] for r in info["replicas"].values()}) == 4


def test_dead_engine_fails_the_request_check(smoke, toy, monkeypatch):
    """An engine whose warm-up raises must not pass as an empty stream:
    the queued request ends FAULTED, later submits raise, stats() names
    the cause, the smoke's request check fails, and stop() reports it."""
    from vtpu.serving import ServingConfig, ServingEngine, Status
    from vtpu.serving.adapters import TransformerSlotModel

    def boom(self, *a, **kw):
        raise RuntimeError("injected compile failure")

    monkeypatch.setattr(TransformerSlotModel, "decode_step", boom)
    eng = ServingEngine(toy.params, toy.cfg, ServingConfig(
        slots=2, prefill_buckets=(32,), max_new_tokens=4))
    eng.start()
    try:
        req = eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4)
    except RuntimeError:
        req = None  # the loop died before the submit landed
    eng._thread.join(timeout=WAIT)
    assert not eng._thread.is_alive()
    if req is not None:
        assert list(req.stream()) == [] and req.status == Status.FAULTED
        with pytest.raises(smoke.SmokeFailure):
            smoke.require_served(eng, [req], [4], wait_s=5.0)
    assert "injected compile failure" in eng.stats()["loop_error"]
    with pytest.raises(RuntimeError, match="loop died"):
        eng.submit(np.arange(1, 9, dtype=np.int32))
    with pytest.raises(smoke.SmokeFailure, match="not alive"):
        smoke.require_alive(eng)
    with pytest.raises(RuntimeError, match="loop died"):
        eng.stop()
    eng.stop()  # reported once; teardown paths stay idempotent


def test_one_request_empty_stream_is_a_failure(smoke):
    """benchmark.one_request against a server that answers 200 and streams
    nothing: a failure record without ttft_ms, never a 0 ms sample; and a
    non-OK terminal line fails a stream that did carry tokens."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Empty(BaseHTTPRequestHandler):
        body = b""

        def log_message(self, *a):
            pass

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            self.send_response(200)
            self.end_headers()
            self.wfile.write(self.body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Empty)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        s = smoke.ttft_client.one_request(url, 8, 4, timeout=10)
        assert s["failed"] == "no first token" and "ttft_ms" not in s
        Empty.body = b'data: {"token": 1}\nstatus: FAULTED\n'
        s = smoke.ttft_client.one_request(url, 8, 4, timeout=10)
        assert s["failed"] == "status FAULTED" and "ttft_ms" not in s
        Empty.body = b'data: {"token": 1}\nstatus: OK\n'
        s = smoke.ttft_client.one_request(url, 8, 4, timeout=10)
        assert "failed" not in s and s["tokens"] == 1 and s["ttft_ms"] > 0
    finally:
        httpd.shutdown()
        httpd.server_close()
    s = smoke.ttft_client.one_request(url, 8, 4, timeout=2)
    assert "failed" in s  # connection refused is a failure too


def test_compile_cache_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: neither the helper nor conftest sets
    another directory. Unset: the directory is <checkout>/.jax_cache."""
    code = ("import sys; sys.path.insert(0, 'tests'); import conftest, jax;"
            "from vtpu.util.jaxcache import place_compile_cache;"
            "print(place_compile_cache());"
            "print(jax.config.jax_compilation_cache_dir)")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    for ambient, want in ((str(tmp_path / "elsewhere"),
                           str(tmp_path / "elsewhere")),
                          (None, str(ROOT / ".jax_cache"))):
        if ambient:
            env["JAX_COMPILATION_CACHE_DIR"] = ambient
        r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert r.stdout.split() == [want, want]
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
