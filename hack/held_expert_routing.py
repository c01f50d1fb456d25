"""How a cell's seeded router spreads a chunk's rows over the experts held
here: the tiles the grouped kernels would run (``vtpu.ops.grouped_ffn``).

    python hack/held_expert_routing.py [--config mimo-v2.5-7l-ep16]
        [--seed 4100001001] [--tokens 2048] [--chunk 512]

The configuration's weights as the benchmark makes them from ``--seed``
(``vbench.weights``), a prompt of ``--tokens`` tokens drawn as its traffic
draws them, one full forward of the program's model on the host, and for
every expert layer and every chunk of ``--chunk`` rows: the held experts
that drew a row, the routed pairs, the most any expert drew, and the live
tiles of ``grouped_ffn.layout`` beside what a uniform router would give
(pairs = rows x top_k x held / E, every held expert with a row). Off the
timed path, on the CPU: a count, never a speed. The experts' products are
done here an expert over its own rows, in a Python loop, so that the later
layers see what they would in the cell.
"""

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from vbench import weights  # noqa: E402
from vtpu.ops import grouped_ffn  # noqa: E402

FORWARD = {"swa": ("vtpu.models.swa", "swa_forward"),
           "latent": ("vtpu.models.latent", "latent_forward"),
           "mla": ("vtpu.models.latent", "latent_forward")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mimo-v2.5-7l-ep16")
    ap.add_argument("--seed", type=int, default=4100001001)
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=512)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "vbench", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    sut = importlib.import_module(f"vbench.sut.{cfg['family']}")
    model = importlib.import_module(FORWARD[cfg["family"]][0])
    w = weights.make_all(args.seed, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"],
                         weights.layer_kinds(ref, cfg))
    params, mc = sut.params_of(cfg, w), sut.model_config(cfg)
    held, top_k = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    published = cfg["n_routed_experts_published"]
    seen = []

    def an_expert_over_its_rows(lp, x, gates, top_k=None):
        del top_k
        g = np.asarray(gates)
        seen.append(g != 0)
        (w_gate, i), (w_up, _), (w_down, _) = (
            lp.stacked(name) for name in ("w_gate", "w_up", "w_down"))
        y = jnp.zeros(x.shape, jnp.float32)
        for h in range(g.shape[1]):
            rows = np.nonzero(g[:, h])[0]
            if not rows.size:
                continue
            xs = x[rows]
            act = (jax.nn.silu((xs @ w_gate[i, h]).astype(jnp.float32))
                   * (xs @ w_up[i, h]).astype(jnp.float32)
                   * g[rows, h][:, None]).astype(x.dtype)
            y = y.at[rows].add((act @ w_down[i, h]).astype(jnp.float32))
        return y.astype(x.dtype)

    model.held_experts_ffn = an_expert_over_its_rows
    rng = np.random.default_rng(args.seed)
    prompt = rng.integers(1, cfg["vocab_size"], args.tokens, dtype=np.int32)
    getattr(model, FORWARD[cfg["family"]][1])(
        params, mc, jnp.asarray(prompt)[None])
    _, tm, tiles = grouped_ffn.plan(args.chunk, held, top_k)
    print(json.dumps({
        "config": args.config, "seed": args.seed, "chunk": args.chunk,
        "held": held, "top_k": top_k, "experts": published,
        "uniform": {"pairs": args.chunk * top_k * held / published,
                    "with_a_row": held, "live_tiles": held}}))
    for layer, hot in enumerate(seen):
        for c in range(0, args.tokens, args.chunk):
            part = hot[c:c + args.chunk]
            at = grouped_ffn.layout(
                jnp.asarray(part, jnp.float32), tm, tiles)
            print(json.dumps({
                "expert_layer": layer, "rows": f"{c}-{c + len(part) - 1}",
                "with_a_row": int(part.any(axis=0).sum()),
                "pairs": int(part.sum()),
                "most_on_one": int(part.sum(axis=0).max()),
                "live_tiles": int(at["live"])}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
