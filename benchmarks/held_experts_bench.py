"""One layer of held experts alone, at the benchmark's shapes: the all-rows
code against each grouped candidate.

    python benchmarks/held_experts_bench.py [--tiny] [--rows 16,96,256,512]
        [--configs mimo-v2.5-7l-ep16,...] [--candidates all_rows,kernel,...]
        [--out f.json]

``vtpu.models.moe.held_experts_ffn``'s two routes over one layer's stacks
at the widths of the three configurations that hold experts (H, D, F,
top_k, E read from their files under ``vbench/configs``), at the rows of
their three programs (a decode step's 16 or 96, the admission bucket's 256,
a chunk's 512; ``--rows`` takes others, up to the 2048 the kernels take). The
gates are each configuration's own router's over seeded weights and
activations, its held columns; ``one_expert`` sends every row to a single
held expert instead (the pair buffer's bound).

Candidates: ``all_rows`` (every held expert over every row under its gate,
the code before PR 41 and the CPU route), ``kernel``
(``vtpu.ops.grouped_ffn``: what ships), ``gmm`` (the pairs sorted expert by
expert into a buffer of T x min(top_k, H) rows, three calls of JAX's
``megablox`` grouped product, the rows' sum through the inverse
permutation) and ``ragged_dot`` (the same buffer through
``jax.lax.ragged_dot``). A row of the table: milliseconds a layer (eight
calls inside one program, each fed by the one before), GB/s against the
bytes of all H experts' weights and against those of the experts that drew
a row, the row slots multiplied beside the routed pairs, and the largest
distance from ``all_rows`` over the mean size of its output. On a TPU the
times are device times; ``--tiny`` interprets a cut-down shape on the CPU
and proves only that the script runs: never a speed.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vtpu.models import moe  # noqa: E402
from vtpu.ops import grouped_ffn  # noqa: E402

CONFIGS = ("mimo-v2.5-7l-ep16", "deepseek-v3.2-5l-ep16", "deepseek-v2-5l-ep8")
CALLS = 8      # a timed program's calls, each fed by the one before
REPEATS = 5    # timed programs; the least counts


def shape_of(name: str, tiny: bool) -> dict:
    """What the layer's shapes and router are, from the configuration."""
    with open(os.path.join(ROOT, "vbench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    s = {"h": cfg["n_routed_experts"], "d": cfg["hidden_size"],
         "f": cfg["moe_intermediate_size"], "k": cfg["num_experts_per_tok"],
         "e": cfg["n_routed_experts_published"],
         "first": cfg["held_experts_first"],
         "n_group": cfg.get("n_group") or 1,
         "topk_group": cfg.get("topk_group") or 1,
         "scale": cfg.get("routed_scaling_factor") or 1.0,
         "softmax": cfg.get("scoring_func") == "softmax"}
    if tiny:
        s.update(d=256, f=256)
    return s


def draw(s: dict, t: int, seed: int, one_expert: bool):
    """(x [T, D] bfloat16, gates [T, H] float32, the layer's stacks)."""
    ks = jax.random.split(jax.random.key(seed), 7)
    h, d, f, e = s["h"], s["d"], s["f"], s["e"]
    x = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
    lp = {"w_gate": jax.random.normal(ks[1], (h, d, f), jnp.bfloat16) * d ** -0.5,
          "w_up": jax.random.normal(ks[2], (h, d, f), jnp.bfloat16) * d ** -0.5,
          "w_down": jax.random.normal(ks[3], (h, f, d), jnp.bfloat16) * f ** -0.5}
    router = jax.random.normal(ks[4], (d, e), jnp.float32) * d ** -0.5
    if one_expert:
        gates = jnp.zeros((t, h), jnp.float32).at[:, h // 2].set(0.5)
    elif s["softmax"]:
        gates = moe.group_limited_route(
            router, x, s["k"], s["n_group"], s["topk_group"], s["scale"])
    else:
        bias = jax.random.uniform(ks[5], (e,), jnp.float32, -0.05, 0.05)
        gates = moe.grouped_route(router, bias, x, s["k"], s["n_group"],
                                  s["topk_group"], s["scale"])
    if not one_expert:
        gates = gates[:, s["first"]:s["first"] + h]
    return x, gates, lp


def sorted_pairs(x, gates, k):
    """The pairs expert by expert in a buffer of ``T * k`` rows rounded up
    to 128: (rows [M, D], their gates [M, 1], the experts' group sizes,
    each pair's place in the buffer [T, k], which of them are pairs)."""
    t, h = gates.shape
    weight, expert = jax.lax.top_k(jnp.abs(gates), k)
    weight = jnp.take_along_axis(gates, expert, axis=1)
    key = jnp.where(weight != 0, expert, h).reshape(-1)
    order = jnp.argsort(key, stable=True)
    m = -(-t * k // 128) * 128
    pad = m - t * k
    rows = jnp.pad(x[order // k], ((0, pad), (0, 0)))
    w = jnp.pad(weight.reshape(-1)[order], (0, pad))[:, None]
    sizes = jnp.bincount(key, length=h + 1)[:h].astype(jnp.int32)
    place = jnp.argsort(order).reshape(t, k)
    return rows, w, sizes, place, weight != 0


def back(y, place, is_pair):
    """A row's pairs summed in float32 through the inverse permutation."""
    return jnp.sum(jnp.where(is_pair[..., None], y[place], 0.0), axis=1)


def weighed(gate, up, w):
    """``silu(gate) * up * the pair's gate`` in float32, in ``gate``'s
    dtype: the all-rows code's activation, a pair a row."""
    return (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
            * w).astype(gate.dtype)


def ragged_dot_ffn(lp, x, gates, k, interpret=False):
    del interpret
    rows, w, sizes, place, is_pair = sorted_pairs(x, gates, min(k, gates.shape[1]))
    gate = jax.lax.ragged_dot(rows, lp["w_gate"], sizes)
    up = jax.lax.ragged_dot(rows, lp["w_up"], sizes)
    act = weighed(gate, up, w)
    y = jax.lax.ragged_dot(act, lp["w_down"], sizes,
                           preferred_element_type=jnp.float32)
    return back(y, place, is_pair)


def _tile(n: int, most: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``most``, else ``n`` whole (``gmm``'s tiles)."""
    return next((b for b in range(most, 0, -128) if n % b == 0), n)


def gmm_ffn(lp, x, gates, k, interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    d, f = lp["w_gate"].shape[1:]
    rows, w, sizes, place, is_pair = sorted_pairs(x, gates, min(k, gates.shape[1]))
    wide = (128, _tile(d, 1024), _tile(f, 512))
    gate = gmm(rows, lp["w_gate"], sizes, x.dtype, wide, interpret=interpret)
    up = gmm(rows, lp["w_up"], sizes, x.dtype, wide, interpret=interpret)
    act = weighed(gate, up, w)
    y = gmm(act, lp["w_down"], sizes, jnp.float32,
            (128, _tile(f, 512), _tile(d, 1024)),
            interpret=interpret)
    return back(y, place, is_pair)


def kernel_ffn(lp, x, gates, k, interpret=False):
    return grouped_ffn.grouped_experts_ffn(
        x, gates, lp["w_gate"][None], lp["w_up"][None], lp["w_down"][None],
        0, k, interpret=interpret)


def all_rows_ffn(lp, x, gates, k, interpret=False):
    del k, interpret
    return moe.held_experts_all_rows(lp, x, gates).astype(jnp.float32)


CANDIDATES = {"all_rows": all_rows_ffn, "kernel": kernel_ffn,
              "gmm": gmm_ffn, "ragged_dot": ragged_dot_ffn}


def timed(fn, lp, x, gates):
    """Least seconds a call over REPEATS programs of CALLS calls each."""
    @jax.jit
    def program(lp, x, gates):
        def call(_, x):
            return x + (fn(lp, x, gates) * 1e-3).astype(x.dtype)
        return jax.lax.fori_loop(0, CALLS, call, x)

    jax.block_until_ready(program(lp, x, gates))
    best = None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(program(lp, x, gates))
        dt = (time.perf_counter() - t0) / CALLS
        best = dt if best is None else min(best, dt)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--rows", default="16,96,256,512")
    ap.add_argument("--configs", default=",".join(CONFIGS))
    ap.add_argument("--candidates", default=",".join(CANDIDATES))
    ap.add_argument("--out", default="chiprun_out/held_experts_bench.json")
    args = ap.parse_args(argv)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or args.tiny):
        print("no TPU: pass --tiny to interpret a cut-down shape on the CPU",
              file=sys.stderr)
        return 2
    rows_of = [int(r) for r in args.rows.split(",")]
    bandwidth = 819e9  # vbench/peaks/TPU_v5_lite.json
    table = []
    for name in args.configs.split(","):
        s = shape_of(name, args.tiny)
        weights = 3 * s["h"] * s["d"] * s["f"] * 2
        for t in rows_of:
            for one in (False, True):
                if one and t != max(rows_of):
                    continue
                x, gates, lp = draw(s, t, 41 + t, one)
                hot = np.asarray(gates != 0)
                pairs, drew = int(hot.sum()), int(hot.any(axis=0).sum())
                _, tm, tiles = grouped_ffn.plan(t, s["h"], s["k"])
                live = int(grouped_ffn.layout(
                    jnp.pad(gates, ((0, -t % 16), (0, 0))), tm, tiles)["live"])
                slots = {"all_rows": s["h"] * t, "kernel": live * tm}
                ref = None
                for cand in args.candidates.split(","):
                    fn = functools.partial(
                        CANDIDATES[cand], k=s["k"], interpret=not on_tpu)
                    out = np.asarray(jax.jit(fn)(lp, x, gates))
                    if cand == "all_rows":
                        ref = out
                    row = {"config": name, "rows": t, "routing":
                           "one_expert" if one else "router",
                           "candidate": cand, "pairs": pairs,
                           "experts_with_rows": drew, "held": s["h"],
                           "row_slots": slots.get(cand)}
                    if ref is not None and cand != "all_rows":
                        row["distance"] = float(
                            np.abs(out - ref).max()
                            / max(np.abs(ref).mean(), 1e-9))
                    if on_tpu:
                        sec = timed(fn, lp, x, gates)
                        row.update(
                            ms=1e3 * sec,
                            gb_s_all_weights=weights / sec / 1e9,
                            gb_s_drawn_weights=(
                                weights * drew / s["h"] / sec / 1e9),
                            least_ms_all_weights=1e3 * weights / bandwidth)
                    table.append(row)
                    print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "table": table}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
