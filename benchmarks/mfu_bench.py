"""MFU + attention-kernel benchmark for the flagship prefill path.

This harness measures, on the chip:

  1. prefill MFU: exact matmul FLOPs of the flagship forward (projections,
     attention score/out, MLP, LM head) / wall time / chip peak. K prefills
     are chained inside ONE executable (lax.scan) and two chain lengths are
     differenced, so the fixed per-call cost (enqueue + the D2H fetch that
     syncs the timing) cancels out of the per-iteration figure.
  2. flash_attention (Pallas) vs causal_attention (XLA) at serving shapes.

Needs a TPU whose ``device_kind`` is in PEAKS (an unknown kind is an error,
never an assumed peak) and then writes build/MFU.json. ``--cpu`` is a tiny
smoke of the harness itself: no peak, no utilization, nothing written.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from vtpu.models import ModelConfig, init_params, prefill  # noqa: E402
from vtpu.ops import causal_attention, flash_attention  # noqa: E402
from vtpu.util.jaxcache import place_compile_cache  # noqa: E402

# device_kind -> (peak bf16 FLOP/s, peak HBM bytes/s) of ONE chip. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def device_peaks() -> tuple:
    """(peak FLOP/s, peak bytes/s) of the device JAX runs on; (None, None)
    off-TPU (the --cpu smoke reports no utilization at all)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None, None
    if dev.device_kind not in PEAKS:
        raise SystemExit(
            f"no peak table entry for device_kind {dev.device_kind!r}; add "
            "its published peaks to PEAKS rather than assuming another "
            "chip's")
    return PEAKS[dev.device_kind]


def _share(value: float, peak) -> float | None:
    return None if peak is None else round(100 * value / peak, 2)


def prefill_flops(cfg: ModelConfig, b: int, s: int) -> int:
    """Matmul FLOPs of one forward pass (2*M*N*K per matmul, full causal
    scores counted as computed)."""
    d, qd, f = cfg.d_model, cfg.qkv_dim, cfg.d_ff
    proj = 4 * 2 * b * s * d * qd  # wq, wk, wv, wo
    attn = 2 * 2 * b * cfg.n_heads * s * s * cfg.head_dim  # scores + out
    mlp = 3 * 2 * b * s * d * f  # gate, up, down
    head = 2 * b * s * d * cfg.vocab
    return cfg.n_layers * (proj + attn + mlp) + head


def timed(fn, *args, iters: int = 5) -> float:
    """Median wall seconds of fn(*args) synced via a tiny D2H fetch."""
    fn(*args)  # compile + warm
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        np.asarray(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def timed_per_iter(make_chain, k_lo: int, k_hi: int, *args,
                   iters: int = 5) -> float:
    """Per-iteration seconds via the TWO-CHAIN-LENGTH DIFFERENCE:
    (t(k_hi) - t(k_lo)) / (k_hi - k_lo). Dividing one chain's wall by its
    length smears the fixed per-call cost (dispatch + the syncing fetch)
    into every number; the difference cancels it exactly."""
    t_lo = timed(make_chain(k_lo), *args, iters=iters)
    t_hi = timed(make_chain(k_hi), *args, iters=iters)
    if t_hi <= t_lo:
        # noise swallowed the compute delta: retry once with more samples,
        # then refuse rather than publish an absurd number
        t_lo = timed(make_chain(k_lo), *args, iters=2 * iters + 1)
        t_hi = timed(make_chain(k_hi), *args, iters=2 * iters + 1)
        if t_hi <= t_lo:
            raise RuntimeError(
                f"two-chain difference unusable: t({k_hi})={t_hi:.4f}s <= "
                f"t({k_lo})={t_lo:.4f}s (noise > compute delta)")
    return (t_hi - t_lo) / (k_hi - k_lo)


def bench_prefill(cfg: ModelConfig, b: int, s: int, k_chain: int) -> dict:
    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (b, s)), jnp.int32)

    def make_chain(length):
        @jax.jit
        def chained(params, tokens):
            # xor-feed the summary back into the tokens so XLA cannot
            # collapse the K iterations; the perturbation keeps ids in range
            def body(carry, _):
                logits, _cache = prefill(params, cfg, tokens ^ (carry & 1))
                return jnp.sum(logits).astype(jnp.int32) & 1, None

            out, _ = jax.lax.scan(body, jnp.int32(0), None, length=length)
            return out
        return chained

    sec = timed_per_iter(make_chain, k_chain, 3 * k_chain, params, tokens)
    flops = prefill_flops(cfg, b, s)
    return {
        "batch": b, "seq": s, "chain": [k_chain, 3 * k_chain],
        "timing": "two-chain-length difference",
        "ms_per_prefill": round(sec * 1e3, 2),
        "tflops_per_prefill": round(flops / 1e12, 3),
        "mfu_percent": _share(flops / sec, device_peaks()[0]),
        "tokens_per_sec": round(b * s / sec),
    }


def bench_attention(b: int, s: int, h: int, dh: int, dtype, k_chain: int = 8) -> dict:
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, dh)), dtype) for _ in range(3))

    def chain(attn_fn):
        def make(length):
            @jax.jit
            def run(q, k, v):
                def body(carry, _):
                    o = attn_fn(q + carry, k, v)
                    return jnp.max(o).astype(q.dtype) * 0, None

                out, _ = jax.lax.scan(body, q.dtype.type(0), None,
                                      length=length)
                return out

            return run
        return make

    flash_s = timed_per_iter(chain(flash_attention), k_chain, 3 * k_chain,
                             q, k, v)
    xla_s = timed_per_iter(chain(causal_attention), k_chain, 3 * k_chain,
                           q, k, v)
    flops = 2 * 2 * b * h * s * s * dh  # scores + out, full causal as computed
    return {
        "shape": [b, s, h, dh], "dtype": str(dtype.__name__ if hasattr(dtype, "__name__") else dtype),
        "timing": "two-chain-length difference",
        "flash_ms": round(flash_s * 1e3, 3),
        "xla_ms": round(xla_s * 1e3, 3),
        "flash_tflops": round(flops / flash_s / 1e12, 1),
        "xla_tflops": round(flops / xla_s / 1e12, 1),
        "flash_speedup": round(xla_s / flash_s, 2),
    }


def bench_decode(cfg: ModelConfig, b: int, prompt_len: int, steps: int,
                 kv_bucket: int = 0, unroll: bool = True) -> dict:
    """Decode throughput + HBM-bandwidth utilization. Decode is
    bandwidth-bound on TPU: every step streams the full weights (and the KV
    cache) through HBM for one token per sequence, so the honest utilization
    metric is bytes-moved / wall / peak-BW, not FLOPs."""
    from vtpu.models import decode_step

    # an undersized read window would silently drop freshly written tokens
    # (decode_layer_loop never errors) and publish wrong bandwidth numbers
    assert prompt_len + steps <= (kv_bucket or cfg.max_seq), (
        prompt_len, steps, kv_bucket)

    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (b, prompt_len)), jnp.int32)
    _, cache = jax.jit(lambda p, t: prefill(p, cfg, t))(params, tokens)
    jax.block_until_ready(cache)

    def make_chain(length):
        @jax.jit
        def chained(params, cache, tok):
            def body(carry, _):
                cache, tok = carry
                logits, cache = decode_step(params, cfg, cache, tok,
                                            kv_bucket=kv_bucket, unroll=unroll)
                return (cache, jnp.argmax(logits, -1).astype(jnp.int32)), None

            (cache, tok), _ = jax.lax.scan(body, (cache, tok), None,
                                           length=length)
            return tok
        return chained

    # capacity guard above uses the LONG chain (steps is the hi length)
    sec_per_step = timed_per_iter(
        make_chain, max(steps // 4, 1), steps, params, cache, tokens[:, -1])
    sec = sec_per_step * steps
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    read_len = kv_bucket or cfg.max_seq
    kv_elems = 2 * cfg.n_layers * b * read_len * cfg.n_heads
    if getattr(cfg, "kv_int8", False):
        # int8 values + one f32 scale per (token, head)
        kv_bytes = kv_elems * (cfg.head_dim + 4)
    else:
        kv_bytes = kv_elems * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
    bytes_per_step = param_bytes + kv_bytes
    return {
        "batch": b, "prompt_len": prompt_len, "steps": steps,
        "kv_bucket": kv_bucket or cfg.max_seq, "unroll": unroll,
        "kv_int8": bool(getattr(cfg, "kv_int8", False)),
        "decode_attn": "xla",
        "timing": "two-chain-length difference",
        "ms_per_step": round(sec / steps * 1e3, 3),
        "tokens_per_sec": round(b * steps / sec),
        "param_bytes_mb": round(param_bytes / 1e6, 1),
        "hbm_gb_per_sec": round(bytes_per_step * steps / sec / 1e9, 1),
        "hbm_bw_utilization_percent": _share(
            bytes_per_step * steps / sec, device_peaks()[1]),
    }


def bench_spec_tick(cfg: ModelConfig, b: int, prompt_len: int, k: int,
                    steps: int, kv_bucket: int = 0, unroll: bool = True) -> dict:
    """Cost of a speculative verify tick vs a plain decode tick.

    The economics of speculation on TPU: decode streams the weights + KV
    window per tick regardless of how many positions ride along, so a
    (k+1)-position verify tick should cost barely more than a 1-token tick —
    the measured ratio IS the breakeven mean-emitted-tokens, and projected
    speedup at mean emitted E is E / ratio. Draft content is irrelevant to
    timing (shapes are static); acceptance only changes how often you tick.
    """
    from vtpu.models.slots import batched_spec_step

    # The chained loop below pins cap=1 so the cache grows at most one token
    # per tick (timing is shape-static, so commit count is irrelevant to the
    # measurement); this guard is therefore exact, not a ~1-token-per-step
    # approximation that accepting traffic could run past.
    assert prompt_len + steps + k + 1 <= (kv_bucket or cfg.max_seq)
    params = jax.jit(lambda key: init_params(key, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab, (b, prompt_len)), jnp.int32)
    _, cache = jax.jit(lambda p, t: prefill(p, cfg, t))(params, tokens)
    jax.block_until_ready(cache)
    draft = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab, (b, k + 1)), jnp.int32)
    active = jnp.ones((b,), bool)
    cap = jnp.ones((b,), jnp.int32)

    def make_chain(length):
        @jax.jit
        def chained(params, cache, draft):
            def body(carry, _):
                cache, draft = carry
                pred, _, cache = batched_spec_step(
                    params, cfg, cache, draft, active, cap,
                    kv_bucket=kv_bucket, unroll=unroll)
                return (cache, pred), None

            (cache, _), _ = jax.lax.scan(body, (cache, draft), None,
                                         length=length)
            return cache["len"]
        return chained

    spec_ms = timed_per_iter(
        make_chain, max(steps // 4, 1), steps, params, cache, draft) * 1e3
    plain = bench_decode(cfg, b, prompt_len, steps, kv_bucket=kv_bucket,
                         unroll=unroll)
    ratio = spec_ms / plain["ms_per_step"]
    return {
        "batch": b, "prompt_len": prompt_len, "spec_tokens": k,
        "kv_bucket": kv_bucket or cfg.max_seq,
        "decode_attn": "xla",
        "timing": "two-chain-length difference",
        "ms_per_verify_tick": round(spec_ms, 3),
        "ms_per_decode_tick": plain["ms_per_step"],
        "verify_cost_ratio": round(ratio, 3),
        # mean emitted tokens per tick at which speculation breaks even;
        # anything above it is speedup (e.g. emitted 3.0 at ratio 1.3 ->
        # 2.3x tokens/sec)
        "breakeven_mean_emitted": round(ratio, 3),
        "projected_speedup_at_mean_emitted": {
            str(e): round(e / ratio, 2) for e in (2, 3, k + 1)
        },
    }


def bench_ssm_decode(b: int, steps: int, on_tpu: bool) -> dict:
    """Selective-SSM decode throughput: O(1) recurrent state, so tokens/sec
    is independent of how long each sequence has run — the contrast point to
    the transformer's cache-read-bound decode."""
    from vtpu.models.ssm import (
        SSMConfig, init_ssm_params, init_ssm_state, ssm_decode_step,
    )

    if on_tpu:
        cfg = SSMConfig(vocab=8192, d_model=1024, n_layers=12, d_state=16,
                        dtype=jnp.bfloat16)
    else:
        cfg = SSMConfig(vocab=256, d_model=64, n_layers=2, d_state=8,
                        dtype=jnp.float32)
    params = jax.jit(lambda k: init_ssm_params(k, cfg))(jax.random.key(0))
    jax.block_until_ready(params)
    state = init_ssm_state(cfg, b)
    tok0 = jnp.zeros((b,), jnp.int32)

    def make_chain(length):
        @jax.jit
        def chained(params, state, tok):
            def body(carry, _):
                state, tok = carry
                logits, state = ssm_decode_step(params, cfg, state, tok)
                return (state, jnp.argmax(logits, -1).astype(jnp.int32)), None

            (state, tok), _ = jax.lax.scan(body, (state, tok), None,
                                           length=length)
            return tok
        return chained

    sec_per_step = timed_per_iter(
        make_chain, max(steps // 4, 1), steps, params, state, tok0)
    param_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(params))
    return {
        "batch": b, "steps": steps,
        "d_model": cfg.d_model, "n_layers": cfg.n_layers,
        "timing": "two-chain-length difference",
        "ms_per_step": round(sec_per_step * 1e3, 3),
        "tokens_per_sec": round(b / sec_per_step),
        "param_bytes_mb": round(param_bytes / 1e6, 1),
    }


def main() -> None:
    cpu_smoke = "--cpu" in sys.argv
    if cpu_smoke:
        jax.config.update("jax_platforms", "cpu")
    place_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not cpu_smoke:
        raise SystemExit(
            f"mfu_bench needs a TPU, JAX found {jax.default_backend()!r}; "
            "--cpu runs the harness smoke at toy size")
    peak_flops, _ = device_peaks()
    if on_tpu:
        cfg = ModelConfig(
            vocab=8192, d_model=1024, n_heads=8, n_layers=12, d_ff=4096,
            max_seq=2048, head_dim=128, dtype=jnp.bfloat16, use_pallas=True,
        )
        shapes = [(16, 1024), (32, 1024), (16, 2048)]
        # long-sequence points: attention cost
        # grows as s^2 while everything else is linear, so these are the
        # shapes where a hand kernel can actually separate from XLA
        attn_shapes = [(16, 1024, 8, 128), (16, 2048, 8, 128), (4, 2048, 8, 128),
                       (2, 4096, 8, 128), (1, 8192, 8, 128)]
        k_chain = 8
        dtype = jnp.bfloat16
    else:  # CPU smoke
        cfg = ModelConfig(
            vocab=512, d_model=128, n_heads=4, n_layers=2, d_ff=256,
            max_seq=256, head_dim=32, dtype=jnp.float32, use_pallas=False,
        )
        shapes = [(2, 128)]
        attn_shapes = [(2, 128, 4, 32)]
        k_chain = 2
        dtype = jnp.float32

    def safe(fn, *a, **kw) -> dict:
        # one unusable measurement (timed_per_iter refusing a noise-swamped
        # delta) must cost its row, not the whole sweep
        try:
            return fn(*a, **kw)
        except Exception as exc:  # noqa: BLE001
            return {"error": str(exc)[:300], "bench": fn.__name__,
                    "args": [repr(x)[:60] for x in a[1:]]}

    out = {"backend": jax.default_backend(),
           "device_kind": jax.devices()[0].device_kind,
           "peak_flops": peak_flops,
           "prefill": [], "attention": [], "decode": []}
    for b, s in shapes:
        r = safe(bench_prefill, cfg, b, s, k_chain)
        out["prefill"].append(r)
        print("prefill", r, flush=True)
    for b, s, h, dh in attn_shapes:
        try:
            r = bench_attention(b, s, h, dh, dtype, k_chain)
        except Exception as exc:  # a kernel limit at an extreme shape is a
            r = {"shape": [b, s, h, dh], "error": str(exc)[:300]}  # result too
        out["attention"].append(r)
        print("attention", r, flush=True)
    if on_tpu:
        long_rows = [r for r in out["attention"]
                     if r.get("shape", [0, 0])[1] >= 4096 and "error" not in r]
        note = (
            "Policy: use_pallas is the flagship default on TPU and the "
            "prefill route engages at FLASH_MIN_SEQ=1024 (the flash_speedup "
            "column of these rows is its basis)."
        )
        if long_rows:
            note += (
                " The kernel earns its keep as sequence grows (s^2 score "
                "traffic vs VMEM-resident single-pass tiles) — see the "
                "s>=4096 rows."
            )
        out["attention_note"] = note
    # full-cache reads vs the serving engine's bucketed read window (the
    # serving default: unrolled layer loop, static window view). The
    # target cells are batches {8, 32} x windows {1024, 2048}, bf16 and
    # int8, all on the XLA op chain (hack/int8_ab.py carries the
    # repeated-measure int8-vs-bf16 verdict per cell).
    decode_shapes = ([(8, 128, 64, 256), (8, 128, 64, 1024), (8, 128, 64, 0),
                      (32, 128, 64, 256), (32, 128, 64, 1024), (32, 128, 64, 0)]
                     if on_tpu else [(2, 32, 4, 0)])
    cfg_q = dataclasses.replace(cfg, kv_int8=True)
    for b, p, steps, bkt in decode_shapes:
        for base in (cfg, cfg_q):
            r = safe(bench_decode, base, b, p, steps, kv_bucket=bkt)
            out["decode"].append(r)
            print("decode", r, flush=True)
    # The fused dense decode kernel has no in-trunk route since r6 (it lost
    # to XLA at every trunk cell); its standalone numbers stay
    # re-checkable via hack/decode_attn_bench.py over
    # benchmarks/decode_attn_kernel.py.
    if on_tpu:
        # Root-cause exhibit for the fori_loop decode inversion: under
        # fori_loop the bounded read dynamic_index_in_dim(ks, l)
        # [:, :bucket] has a loop-carried layer index, which XLA lowers to a
        # materialized slice copy — at batch 32 that copy costs more than
        # streaming the full cache. The serving engine now unrolls.
        r = safe(bench_decode, cfg, 32, 128, 64, kv_bucket=256, unroll=False)
        out["decode_fori_exhibit"] = r
        out["decode_note"] = (
            "decode_fori_exhibit is the fori_loop layer loop: its "
            "dynamic-layer-index bounded read lowers to a materialized slice "
            "copy, which is why the serving engine unrolls the layer loop."
        )
        print("decode_fori_exhibit", r, flush=True)
    # speculative verify-tick cost (r4+): the ratio to a plain decode tick
    # is the breakeven mean-emitted-tokens for speculation
    out["spec"] = []
    spec_shapes = ([(8, 128, 4, 64, 256), (32, 128, 4, 64, 256),
                    (8, 1024, 4, 64, 2048), (32, 1024, 4, 64, 2048)] if on_tpu
                   else [(2, 32, 4, 4, 0)])
    for b, p, k, steps, bkt in spec_shapes:
        r = safe(bench_spec_tick, cfg, b, p, k, steps, kv_bucket=bkt)
        out["spec"].append(r)
        print("spec", r, flush=True)

    out["ssm_decode"] = []
    for b, steps in ([(8, 64), (32, 64)] if on_tpu else [(2, 4)]):
        r = safe(bench_ssm_decode, b, steps, on_tpu)
        out["ssm_decode"].append(r)
        print("ssm_decode", r, flush=True)
    if on_tpu:
        (ROOT / "build").mkdir(exist_ok=True)
        (ROOT / "build" / "MFU.json").write_text(
            json.dumps(out, indent=2) + "\n")


if __name__ == "__main__":
    main()
