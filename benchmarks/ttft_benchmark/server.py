"""Minimal streaming JAX inference server for the TTFT benchmark.

Serves the flagship vtpu.models transformer. POST /generate with
``{"prompt_len": N, "max_tokens": M}`` streams one line per generated token
(`data: {"token": t, "ts": server_time}`) so the client can timestamp the
first token, then one `status: <Request.status>` line, mirroring the
reference's vLLM streaming benchmark server shape (reference
benchmarks/ai-benchmark/benchmark.py client contract).

The served model is the flagship preset and needs a TPU: without one the
server exits non-zero unless `--preset cpu` (a toy model for the CPU tests)
was asked for by name.

When launched inside a vtpu-scheduled pod, libvtpu caps this process's HBM
and TensorCore duty per the pod's fractional ask — no server-side changes.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

# runnable as a plain script (the deployment Jobs do `python .../server.py`):
# put the repo root on sys.path so `vtpu` imports without an install
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

log = logging.getLogger("ttft-server")


def preset(name: str):
    """(ModelConfig, ServingConfig) of a named preset: ``tpu`` is the
    flagship the benchmark serves, ``cpu`` a toy for the CPU tests. Nothing
    here looks at the backend — the caller names the preset."""
    import jax.numpy as jnp

    from vtpu.models import ModelConfig
    from vtpu.serving import ServingConfig

    if name == "tpu":
        cfg = ModelConfig(
            vocab=8192, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
            max_seq=1280, head_dim=128, dtype=jnp.bfloat16, use_pallas=True,
        )
        serving = ServingConfig(slots=4, prefill_buckets=(128, 256, 512, 1024),
                                max_new_tokens=64)
    elif name == "cpu":
        cfg = ModelConfig(
            vocab=512, d_model=128, n_heads=4, n_layers=2, d_ff=256,
            max_seq=160, head_dim=32, dtype=jnp.float32, use_pallas=False,
        )
        serving = ServingConfig(slots=2, prefill_buckets=(32, 64, 128),
                                max_new_tokens=32)
    else:
        raise ValueError(f"unknown preset {name!r}")
    return cfg, serving


class Engine:
    """The vtpu.serving continuous-batching engine behind a streaming API.

    Concurrent /generate requests occupy independent cache slots and decode
    jointly — the real multi-request serving path, not a lock-serialized
    batch-1 loop."""

    def __init__(self, preset_name: str = "tpu"):
        import jax

        from vtpu.models import init_params
        from vtpu.serving import ServingEngine

        cfg, serving = preset(preset_name)
        if preset_name == "tpu" and jax.default_backend() != "tpu":
            raise RuntimeError(
                f"preset 'tpu' needs a TPU, JAX found "
                f"{jax.default_backend()!r}; pass --preset cpu for the toy "
                "model")
        self.cfg = cfg
        self._rng = np.random.default_rng(0)
        self._rng_lock = threading.Lock()
        self.params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
        jax.block_until_ready(self.params)
        self.engine = ServingEngine(self.params, cfg, serving)
        self.engine.start()
        # warm EVERY prefill bucket (plus the shared decode step) so no real
        # request ever pays an XLA compile — this is a TTFT benchmark. A
        # bucket that streams nothing is a dead engine, not a fast one.
        for bucket in serving.prefill_buckets:
            got = sum(1 for _ in self.generate(bucket, 2))
            if got != 2:
                raise RuntimeError(
                    f"warm-up at bucket {bucket} streamed {got} of 2 tokens")

    def trace_stats(self) -> dict:
        """Engine-side span telemetry, re-derived from the trace substrate
        (vtpu/obs): TTFT/ITL/queue-wait percentiles as the ENGINE measured
        them (submit -> first delivery), served at GET /stats so the
        benchmark client can print them next to its own wall-clock
        percentiles — the server-side numbers exclude only the HTTP hop.
        queue_wait_* + prefill_exec_* split TTFT into its waiting vs
        prefilling components (both reservoirs fed off the trace spans),
        so a disagg-vs-cosched TTFT delta is attributable; the disagg
        handoff counters ride along when the role split is on."""
        s = self.engine.stats()
        return {k: s[k] for k in (
            "ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
            "itl_p50_ms", "itl_p99_ms",
            "queue_wait_p50_ms", "queue_wait_p99_ms",
            "prefill_exec_p50_ms", "prefill_exec_p99_ms",
            "generated_tokens", "decode_ticks", "device_gets_per_tick",
            "disagg", "handoffs", "handoff_copies", "prefill_backlog",
            "tick_phase_ms", "trace_events_recorded")}

    def submit(self, prompt_len: int, max_tokens: int):
        """Submit one random prompt; raises if the engine cannot take it
        (a dead serving loop raises here, before any HTTP status is sent)."""
        limit = self.engine.serving.prefill_buckets[-1]
        prompt_len = max(1, min(prompt_len, limit))
        # keep prompt + generation inside the KV cache; a request asking for
        # more tokens than fit is clamped, never allowed to wrap the cache
        max_tokens = max(1, min(max_tokens, self.cfg.max_seq - prompt_len - 1))
        # numpy, not an eager jax.random op: a device op of a new shape on
        # the request thread would compile once per distinct prompt length
        with self._rng_lock:
            tokens = self._rng.integers(
                0, self.cfg.vocab, (prompt_len,), dtype=np.int32)
        return self.engine.submit(tokens, max_new_tokens=max_tokens)

    @staticmethod
    def stream(req):
        """Yield (token_id, monotonic_ts) per generated token of *req*."""
        try:
            for token in req.stream():
                yield token, time.monotonic()
        finally:
            req.cancel()  # client gone mid-stream: free the slot next tick

    def generate(self, prompt_len: int, max_tokens: int):
        return self.stream(self.submit(prompt_len, max_tokens))


def make_handler(engine: Engine):
    class Handler(BaseHTTPRequestHandler):
        def log_message(self, fmt, *args):
            log.debug(fmt, *args)

        def do_GET(self):
            if self.path == "/healthz":
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"ok")
            elif self.path == "/stats":
                body = json.dumps(engine.trace_stats()).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.end_headers()
                self.wfile.write(body)
            else:
                self.send_response(404)
                self.end_headers()

        def do_POST(self):
            if self.path != "/generate":
                self.send_response(404)
                self.end_headers()
                return
            length = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(length) or b"{}")
            prompt_len = int(req.get("prompt_len", 128))
            max_tokens = int(req.get("max_tokens", 16))
            try:
                handle = engine.submit(prompt_len, max_tokens)
            except (RuntimeError, ValueError) as exc:
                self.send_error(503, explain=repr(exc))
                return
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.end_headers()
            for token, ts in engine.stream(handle):
                line = json.dumps({"token": token, "ts": ts})
                self.wfile.write(f"data: {line}\n".encode())
                self.wfile.flush()
            # the typed terminal: a stream the engine ended early (FAULTED,
            # shed) is told apart from one that ran to its budget
            self.wfile.write(f"status: {handle.status}\n".encode())
            self.wfile.flush()

    return Handler


def main() -> None:
    parser = argparse.ArgumentParser("ttft-server")
    parser.add_argument("--port", type=int, default=8100)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--preset", default="tpu", choices=["tpu", "cpu"])
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    import jax

    from vtpu.util.jaxcache import place_compile_cache

    if args.preset == "cpu":
        jax.config.update("jax_platforms", "cpu")
    place_compile_cache()

    engine = Engine(args.preset)
    httpd = ThreadingHTTPServer((args.host, args.port), make_handler(engine))
    log.info("ttft server on :%d (model d=%d L=%d)", args.port,
             engine.cfg.d_model, engine.cfg.n_layers)
    httpd.serve_forever()


if __name__ == "__main__":
    main()
