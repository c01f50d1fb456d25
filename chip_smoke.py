"""The quickest proof that the serving path still starts on the chip.

    python chip_smoke.py

One process, one pass through the entry points a user would call, at the
full width of the flagship (d_model 1024, 12 layers, 8 heads x 128, d_ff
4096, vocab 8192, bf16; weights random from a seed):

1. the TTFT server (benchmarks/ttft_benchmark/server.py's own ``tpu`` preset
   behind its own HTTP handler) answering benchmark.py's ``one_request``;
2. a paged engine on the same weights (kv_page + kv_swap + prefill_chunk,
   read window up to 2048), bf16 then int8 KV: the fused kernel route must
   have run COMPILED (a ``tpu_custom_call`` in the engine's decode step, no
   pool gather), and one teacher-forced decode step must agree between the
   kernel and gather routes on logits;
3. the multi-tick device loop (decode_loop_k=4), then fused speculation
   (spec_tokens=3) on top of it;
4. with four or more chips: a ('tp',) x 4 engine, and four one-chip replicas
   under one EngineFleet with one session migrated between chips.

Every check is a ``require`` that ends the run non-zero; nothing is caught
and turned into a warning. Timings are printed as information under the
device's name and gate nothing. Without a TPU the script exits 1 before
building any model — there is no CPU mode and no small preset here; the
check functions take their sizes as arguments so tests/test_chip_smoke.py
can call them at toy size on the CPU.

The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import importlib.metadata
import importlib.util
import json
import os
import queue
import statistics
import sys
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Optional

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vtpu.ops.decode_attn import count_pool_gathers  # noqa: E402
from vtpu.serving import (  # noqa: E402
    EngineFleet, FleetConfig, ServingConfig, ServingEngine)
from vtpu.serving.adapters import TransformerSlotModel  # noqa: E402
from vtpu.serving.engine import Status, Terminal  # noqa: E402
from vtpu.serving.fleet import RoutePolicy  # noqa: E402
from vtpu.util.jaxcache import place_compile_cache  # noqa: E402


def _load(name: str, *path: str):
    """Import a script of the repo by path (the ttft pair are scripts, not
    a package, and carry generic file names)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ttft_server = _load("ttft_server", "benchmarks", "ttft_benchmark", "server.py")
ttft_client = _load("ttft_client", "benchmarks", "ttft_benchmark",
                    "benchmark.py")


class SmokeFailure(Exception):
    """A check did not hold. Never caught inside this script."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def say(phase: str, **info) -> None:
    print(f"[{phase}] " + json.dumps(info, default=str), flush=True)


# ------------------------------------------------------------ shared pieces


def prompt_tokens(vocab: int, n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        1, vocab, (n,), dtype=np.int32)


def repetitive_prompt(vocab: int, n: int, seed: int,
                      period: int = 8) -> np.ndarray:
    motif = prompt_tokens(vocab, period, seed)
    return np.tile(motif, -(-n // period))[:n]


def collect(req, wait_s: float) -> list:
    """Every token of *req*, each awaited at most *wait_s*: a stream that
    stalls is a failure, never a hang."""
    out = []
    while True:
        try:
            tok = req.out.get(timeout=wait_s)
        except queue.Empty:
            raise SmokeFailure(
                f"request {req.rid} stalled: no token in {wait_s:.0f}s "
                f"after {len(out)} tokens") from None
        if tok is None or isinstance(tok, Terminal):
            return out
        out.append(tok)


def require_served(eng: ServingEngine, reqs: list, budgets: list,
                   wait_s: float) -> list:
    """Collect every stream and hold it to its contract: exactly the
    tokens asked for, terminal OK, and afterwards a live loop thread that
    recorded no error."""
    streams = [collect(r, wait_s) for r in reqs]
    for req, toks, want in zip(reqs, streams, budgets):
        require(len(toks) == want,
                f"request {req.rid}: {len(toks)} tokens, asked {want} "
                f"(status {req.status})")
        require(req.status == Status.OK,
                f"request {req.rid}: status {req.status}, not OK")
    require_alive(eng)
    return streams


def require_alive(eng: ServingEngine) -> None:
    require(eng._thread is not None and eng._thread.is_alive(),
            "serving loop thread is not alive")
    error = eng.stats()["loop_error"]
    require(error is None, f"serving loop recorded {error}")


def started(params, cfg, serving: ServingConfig, warm_prompt: int,
            wait_s: float, mesh=None):
    """Build + start an engine and serve one 2-token request through it, so
    every executable of its loop is compiled: returns (engine, set-up s)."""
    t0 = time.perf_counter()
    eng = ServingEngine(params, cfg, serving, mesh=mesh)
    eng.start()
    req = eng.submit(prompt_tokens(cfg.vocab, warm_prompt, seed=99),
                     max_new_tokens=2)
    require_served(eng, [req], [2], wait_s)
    return eng, round(time.perf_counter() - t0, 1)


# ------------------------------------------------------------- 1. the server


def check_server(engine, plan: list, in_flight: int, wait_s: float) -> dict:
    """Drive *engine* (a ttft server ``Engine``) over HTTP on a loopback
    port with benchmark.py's ``one_request``: ``plan`` is [(prompt_len,
    max_tokens)], ``in_flight`` of them at a time."""
    httpd = ThreadingHTTPServer(("127.0.0.1", 0),
                                ttft_server.make_handler(engine))
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    before = engine.engine.stats()
    try:
        with concurrent.futures.ThreadPoolExecutor(in_flight) as pool:
            futs = [pool.submit(ttft_client.one_request, url, plen, ntok,
                                wait_s) for plen, ntok in plan]
            samples = [f.result(timeout=wait_s + 30) for f in futs]
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=10)
    for (plen, ntok), s in zip(plan, samples):
        what = f"request prompt={plen} tokens={ntok}"
        require("failed" not in s, f"{what} failed: {s.get('failed')}")
        require(s["http"] == 200, f"{what}: HTTP {s['http']}")
        require(s["tokens"] == ntok,
                f"{what}: received {s['tokens']} tokens")
        require(s["status"] == Status.OK, f"{what}: status {s['status']}")
    require_alive(engine.engine)
    after = engine.engine.stats()
    asked = sum(n for _, n in plan)
    got = after["generated_tokens"] - before["generated_tokens"]
    require(got == asked,
            f"stats generated_tokens moved by {got}, asked {asked}")
    # a request of n tokens takes n-1 decode ticks after its prefill token;
    # requests share ticks, and the pipelined loop may dispatch one
    # lookahead tick per request that is dropped at delivery
    ticks = after["decode_ticks"] - before["decode_ticks"]
    lo = max(n for _, n in plan) - 1
    hi = asked + len(plan)
    require(lo <= ticks <= hi,
            f"decode_ticks moved by {ticks}, outside [{lo}, {hi}]")
    gaps = [g for s in samples for g in s["gaps_ms"]]
    return {"requests": len(plan), "tokens": asked, "decode_ticks": ticks,
            "ttft_ms_p50": round(statistics.median(
                s["ttft_ms"] for s in samples), 2),
            "itl_ms_p50": round(statistics.median(gaps), 3)}


# ------------------------------------------------------- 2. the paged engine


def engine_decode_hlo(eng: ServingEngine, bucket: int) -> str:
    """Compiled text of the engine's OWN sampled decode step at read window
    *bucket*, lowered at exactly the shapes _warm_executables compiled it
    at (abstract operands: nothing is executed or donated)."""

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    b = eng.serving.slots
    return eng._decode_sampled.lower(
        eng.params, jax.tree.map(aval, eng.state),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool), aval(eng._rng),
        bucket, unroll=eng._unroll).compile().as_text()


# Kernel route vs gather route, one decode step on the same state, as
# max|dlogit| / max|logit| over all slots x vocab logits. The two routes are
# the same mathematics in a different order (online softmax per page vs the
# whole window; the kernel's probabilities stay float32, the gather route
# rounds them to bf16):
# in float32 they agree to 1e-5 on the CPU (tests/test_paged_attn_kernel.py
# holds streams token-equal there), so a difference here is bf16 rounding
# carried through 12 random-weight layers. Measured (PR 21): 0.025 bf16 and
# 0.023 int8 KV on one v5e chip, 0.033 under ('tp',) x 4, and 0.030 with the
# kernel INTERPRETED on the CPU at the same depth in bf16 — the dtype's
# noise, not the compiled kernel's. The bound is 2^-4, about twice the
# worst of those: it catches what bf16 can show (a wrong page, a misplaced
# scale, a broken accumulator: all O(1)); an off-by-one in a mask is the
# exact float32 CPU tests' to catch, not this one's.
ROUTE_LOGIT_RTOL = 2.0 ** -4


def check_trunk_routes(params, cfg, page: int, window: int, slots: int,
                       free_steps: int, timed_steps: int, reps: int,
                       mesh=None) -> dict:
    """The decode trunk at read window *window* on the kernel route and on
    the gather route, from the SAME seeded pool state: one teacher-forced
    step compared on logits (gated), then a free-running greedy stream per
    route (equality printed, not gated: bf16 argmax forks on rounding),
    then ms per tick per route (printed)."""
    from vtpu.models.transformer import kv_quantized

    margin = 1 + free_steps + reps * timed_steps + page
    require(window - margin >= page, "window too small for the step plan")
    models = {r: TransformerSlotModel(params, cfg, mesh=mesh, kv_page=page,
                                      paged_attn=r)
              for r in ("kernel", "gather")}
    wp = window // page
    table = np.zeros((slots, cfg.max_seq // page), np.int32)
    for i in range(slots):
        table[i, :wp] = 1 + i * wp + np.arange(wp)
    lens = np.asarray([max(1, (window - margin) >> i) for i in range(slots)],
                      np.int32)

    def seeded_state(model):
        state = model.init_state(slots)
        shape = state["k"].shape
        keys = jax.random.split(jax.random.key(7), 4)

        def fill(state):
            out = dict(state, table=jnp.asarray(table), len=jnp.asarray(lens))
            if kv_quantized(cfg):
                for name, key in (("k", keys[0]), ("v", keys[1])):
                    out[name] = jax.random.randint(
                        key, shape, -127, 128, jnp.int32).astype(jnp.int8)
                for name, key in (("k_scale", keys[2]), ("v_scale", keys[3])):
                    out[name] = jax.random.uniform(
                        key, shape[:-1], jnp.float32, 0.5 / 127, 1.5 / 127)
            else:
                for name, key in (("k", keys[0]), ("v", keys[1])):
                    out[name] = jax.random.normal(key, shape, cfg.dtype)
            return out

        # donated and sharded like the pool it replaces: never two pools
        return jax.jit(fill, donate_argnums=(0,),
                       out_shardings=jax.tree.map(
                           lambda x: x.sharding, state))(state)

    steps = {r: jax.jit(m.decode_step, static_argnames=("kv_bucket", "unroll"),
                        donate_argnums=(1,)) for r, m in models.items()}
    states = {r: seeded_state(m) for r, m in models.items()}
    tokens = jnp.asarray(prompt_tokens(cfg.vocab, slots, seed=11))
    active = jnp.ones((slots,), bool)

    def step(route, toks):
        logits, states[route] = steps[route](
            models[route].params, states[route], toks, active, window,
            unroll=True)
        return logits

    # teacher-forced: same tokens, same state, one step per route
    first = {r: np.asarray(step(r, tokens), np.float32) for r in steps}
    require(np.isfinite(first["kernel"]).all()
            and np.isfinite(first["gather"]).all(), "non-finite logits")
    require(first["kernel"].shape == (slots, cfg.vocab),
            f"logits shape {first['kernel'].shape}")
    delta = first["kernel"] - first["gather"]
    rel = float(np.abs(delta).max() / np.abs(first["gather"]).max())
    rel_l2 = float(np.linalg.norm(delta) / np.linalg.norm(first["gather"]))
    require(rel <= ROUTE_LOGIT_RTOL,
            f"kernel vs gather logits differ by {rel:.4f} of max|logit| "
            f"(> {ROUTE_LOGIT_RTOL})")
    # free-running greedy from there, each route feeding itself
    streams = {}
    for r in steps:
        toks, out = jnp.argmax(first[r], -1).astype(jnp.int32), []
        for _ in range(free_steps):
            out.append(np.asarray(toks))
            toks = jnp.argmax(step(r, toks), -1).astype(jnp.int32)
        streams[r] = np.stack(out)
    differ = np.nonzero((streams["kernel"] != streams["gather"]).any(1))[0]
    # ms per tick: fixed tokens, one sync per timed run, routes alternating
    ms = {r: [] for r in steps}
    for _ in range(reps):
        for r in steps:
            t0 = time.perf_counter()
            for _ in range(timed_steps):
                logits = step(r, tokens)
            jax.block_until_ready(logits)
            ms[r].append((time.perf_counter() - t0) / timed_steps * 1e3)
    return {"window": window, "slots": slots, "logit_rel_diff": round(rel, 5),
            "logit_rel_l2": round(rel_l2, 5),
            "free_run_equal": differ.size == 0,
            "free_run_first_fork": int(differ[0]) if differ.size else None,
            "ms_per_tick": {r: round(statistics.median(v), 3)
                            for r, v in ms.items()}}


def check_paged(params, cfg, serving: ServingConfig, plan: list,
                kernel_bucket: int, kernel_marker: Optional[str],
                wait_s: float, mesh=None) -> dict:
    """Serve *plan* ([(prompt_len, max_new)]) through a paged engine and
    prove which route ran: kernel ticks counted, the engine's decode step
    at *kernel_bucket* free of pool gathers and, where *kernel_marker* is
    given (``tpu_custom_call`` on the chip), containing it — so neither an
    interpreted kernel nor a quiet gather can pass."""
    eng, setup_s = started(params, cfg, serving, plan[0][0], wait_s,
                           mesh=mesh)
    try:
        reqs = [eng.submit(prompt_tokens(cfg.vocab, n, seed=i),
                           max_new_tokens=new)
                for i, (n, new) in enumerate(plan)]
        require_served(eng, reqs, [new for _, new in plan], wait_s)
        stats = eng.stats()
    finally:
        eng.stop()
    require(stats["paged_attn_kernel_ticks"] > 0,
            f"no tick took the kernel route (gather ticks "
            f"{stats['paged_attn_gather_ticks']}, windows "
            f"{stats['kv_bucket_hist']})")
    require(stats["prefill_chunks"] > 0, "no prompt took chunked prefill")
    hlo = engine_decode_hlo(eng, kernel_bucket)
    if kernel_marker is not None:
        require(kernel_marker in hlo,
                f"no {kernel_marker} in the decode step at window "
                f"{kernel_bucket}: the kernel did not compile for the chip")
    heads = cfg.n_heads // (mesh.shape["tp"] if mesh is not None else 1)
    gathers = count_pool_gathers(
        hlo, serving.slots * kernel_bucket * heads * cfg.head_dim)
    require(gathers == 0,
            f"{gathers} pool gathers in the kernel-route decode step")
    return {"setup_s": setup_s, "tokens": stats["generated_tokens"],
            "kernel_ticks": stats["paged_attn_kernel_ticks"],
            "gather_ticks": stats["paged_attn_gather_ticks"],
            "prefill_chunks": stats["prefill_chunks"],
            "kernel_calls_in_hlo": (hlo.count(kernel_marker)
                                    if kernel_marker else None)}


# ----------------------------------- 3. device loop and fused speculation


def check_device_loop(params, cfg, serving: ServingConfig, prompt_len: int,
                      budget: int, wait_s: float) -> dict:
    """``serving.decode_loop_k`` ticks per flush (with ``spec_tokens`` on
    top when set) on repetitive prompts, one per slot."""
    eng, setup_s = started(params, cfg, serving, prompt_len, wait_s)
    try:
        reqs = [eng.submit(repetitive_prompt(cfg.vocab, prompt_len, seed=i),
                           max_new_tokens=budget)
                for i in range(serving.slots)]
        require_served(eng, reqs, [budget] * len(reqs), wait_s)
        stats = eng.stats()
    finally:
        eng.stop()
    require(stats["decode_loop_k"] == serving.decode_loop_k,
            f"engine resolved decode_loop_k={stats['decode_loop_k']}")
    require(stats["loop_flushes"] > 0, "the device loop never flushed")
    if serving.spec_tokens:
        require(stats["fused_spec"],
                f"speculation did not fuse: {stats['spec_disabled_reason']}")
    return {"setup_s": setup_s, "loop_flushes": stats["loop_flushes"],
            "decode_ticks": stats["decode_ticks"],
            "spec_ticks": stats["spec_ticks"],
            "mean_emitted_per_spec_tick":
                stats["mean_emitted_per_spec_tick"],
            "device_gets_per_token": stats["device_gets_per_token"]}


# ------------------------------------------------------------ 4. four chips


class PinPolicy(RoutePolicy):
    """Route each submit to the replica named in ``target``."""

    target = ""

    def score(self, name, signals):
        return 1.0 if name == self.target else 0.0


def check_pinned_replicas(params, cfg, serving: ServingConfig, devices: list,
                   prompt_len: int, budget: int, wait_s: float) -> dict:
    """One single-chip replica per device under one EngineFleet in this
    process: each replica's params and pool sit on ITS device, each serves,
    and one session migrates from the first replica to the last."""
    names = [f"r{i}" for i in range(len(devices))]
    t0 = time.perf_counter()
    engines = {n: ServingEngine(jax.device_put(params, d), cfg, serving)
               for n, d in zip(names, devices)}
    for eng in engines.values():
        eng.start()
    warm = {n: eng.submit(prompt_tokens(cfg.vocab, prompt_len, seed=99),
                          max_new_tokens=2) for n, eng in engines.items()}
    for n, eng in engines.items():
        require_served(eng, [warm[n]], [2], wait_s)
    setup_s = round(time.perf_counter() - t0, 1)
    policy = PinPolicy()
    fleet = EngineFleet(engines, FleetConfig(route_policy=policy))
    fleet.start()
    try:
        reqs = []
        for i, n in enumerate(names):
            policy.target = n
            # the first session is the traveller: a long budget keeps it
            # mid-stream while it is moved
            reqs.append(fleet.submit(
                prompt_tokens(cfg.vocab, prompt_len, seed=i),
                max_new_tokens=budget * (4 if i == 0 else 1)))
        head = [reqs[0].out.get(timeout=wait_s) for _ in range(2)]
        require(not any(isinstance(t, Terminal) for t in head),
                f"the travelling session ended early: {reqs[0].status}")
        rep = fleet.migrate_session(reqs[0], names[-1], timeout=wait_s)
        require(rep["path"] in ("resident", "host", "recompute"),
                f"migration took path {rep['path']!r}")
        tail = collect(reqs[0], wait_s)
        require(len(head) + len(tail) == 4 * budget
                and reqs[0].status == Status.OK,
                f"migrated session: {len(head) + len(tail)} of "
                f"{4 * budget} tokens, status {reqs[0].status}")
        for req in reqs[1:]:
            toks = collect(req, wait_s)
            require(len(toks) == budget and req.status == Status.OK,
                    f"replica request: {len(toks)} of {budget} tokens, "
                    f"status {req.status}")
        per = {}
        for n, dev in zip(names, devices):
            eng = engines[n]
            require_alive(eng)
            for leaf in (*jax.tree.leaves(eng.params),
                         *jax.tree.leaves(eng.state)):
                require(leaf.devices() == {dev},
                        f"replica {n}: an array sits on {leaf.devices()}, "
                        f"not {dev}")
            st = eng.stats()
            require(st["generated_tokens"] > 2,
                    f"replica {n} served nothing after warm-up")
            mem = dev.memory_stats()
            if mem is not None:  # the CPU backend reports none
                held = sum(x.nbytes for x in jax.tree.leaves(eng.params))
                require(mem["bytes_in_use"] >= held,
                        f"replica {n}: device holds {mem['bytes_in_use']} "
                        f"bytes, its params alone are {held}")
            per[n] = {"device": str(dev), "tokens": st["generated_tokens"],
                      "bytes_in_use": mem and mem["bytes_in_use"]}
        require(engines[names[-1]].stats()["generated_tokens"]
                > budget + 2, "the migrated session did not decode on its "
                "destination")
    finally:
        fleet.stop()
    return {"setup_s": setup_s, "migration": rep["path"],
            "migration_bytes": rep["bytes"], "replicas": per}


# --------------------------------------------------------------- the run


def flagship_plans(cfg) -> dict:
    """Sizes of the chip run. Buckets and admit sizes are kept few: a cold
    run compiles every executable of every engine here."""
    long_cfg = dataclasses.replace(cfg, max_seq=2048)
    paged = ServingConfig(
        slots=4, prefill_buckets=(1024,), prefill_batch_sizes=(1,),
        max_new_tokens=32, kv_page=16, kv_swap=64, prefill_chunk=256)
    loop_cfg = dataclasses.replace(cfg, max_seq=512)
    loop = ServingConfig(
        slots=4, prefill_buckets=(128,), prefill_batch_sizes=(1,),
        max_new_tokens=48, kv_page=16, decode_loop_k=4)
    return {
        # 128 and 1024 are the ends of the server's bucket range; 1024 is
        # where the model routes prefill through flash_attention
        "server": [(100, 16), (1000, 32), (128, 64), (1024, 16),
                   (60, 24), (900, 48), (512, 16), (300, 32)],
        "long_cfg": long_cfg, "paged": paged,
        # 1100 > the largest bucket: chunked prefill, then decode at read
        # window 2048 (where int8 routes to the kernel on auto); 900 sits
        # in bucket 1024 (where bf16 does)
        "paged_plan": [(1100, 24), (900, 32), (100, 32), (300, 16)],
        "loop_cfg": loop_cfg, "loop": loop,
        "spec": dataclasses.replace(loop, spec_tokens=3),
        "replica": dataclasses.replace(
            loop, decode_loop_k=None, kv_swap=64, prefill_chunk=128),
    }


def main() -> int:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}")
    print(f"versions: jax={jax.__version__} "
          f"jaxlib={importlib.metadata.version('jaxlib')} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    if info["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this script has no CPU mode",
              file=sys.stderr)
        return 1
    print(f"compile cache: {place_compile_cache()}", flush=True)
    dev = f"{info['kind']} x{info['count']}"
    wait_s = 300.0
    t_start = time.perf_counter()

    t0 = time.perf_counter()
    server = ttft_server.Engine("tpu")
    setup_s = round(time.perf_counter() - t0, 1)
    plans = flagship_plans(server.cfg)
    params, cfg = server.params, server.cfg
    say("server", device=dev, setup_s=setup_s,
        **check_server(server, plans["server"], in_flight=4, wait_s=wait_s))
    server.engine.stop()
    del server

    long_cfg = plans["long_cfg"]
    for name, pcfg, bucket in (
            ("paged bf16", long_cfg, 1024),
            ("paged int8", dataclasses.replace(long_cfg, kv_int8=True), 2048)):
        say(name, device=dev, **check_paged(
            params, pcfg, plans["paged"], plans["paged_plan"],
            kernel_bucket=bucket, kernel_marker="tpu_custom_call",
            wait_s=wait_s))
        say(name + " routes", device=dev, **check_trunk_routes(
            params, pcfg, page=16, window=1024, slots=4, free_steps=32,
            timed_steps=32, reps=3))

    for name, serving in (("device loop k=4", plans["loop"]),
                          ("fused spec k=4 K=3", plans["spec"])):
        say(name, device=dev, **check_device_loop(
            params, plans["loop_cfg"], serving, prompt_len=96, budget=48,
            wait_s=wait_s))

    if info["count"] >= 4:
        from jax.sharding import Mesh

        chips = jax.devices()[:4]
        say("tp=4 paged", device=dev, **check_paged(
            params, long_cfg, plans["paged"], plans["paged_plan"],
            kernel_bucket=1024, kernel_marker="tpu_custom_call",
            wait_s=wait_s, mesh=Mesh(np.array(chips), ("tp",))))
        say("four replicas", device=dev, **check_pinned_replicas(
            params, plans["loop_cfg"], plans["replica"], chips,
            prompt_len=96, budget=48, wait_s=wait_s))
    else:
        print(f"multichip: not run, {info['count']} device(s)", flush=True)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices())
    say("done", device=dev, wall_s=round(time.perf_counter() - t_start, 1),
        peak_bytes_in_use=peak)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
