"""A benchmark root for the CPU tests: the repo's own BENCHMARK.json and
vbench/ data copied to a temporary directory, with a toy configuration of
each family, two toy mixes, two toy cells, one metric and the CPU's row of
peaks ADDED as files and entries: nothing that was there is edited, which
is how a later PR adds a cell."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_BASE = dict(
    hidden_size=64, num_attention_heads=2, head_dim=32, num_hidden_layers=2,
    vocab_size=512, max_position_embeddings=256, rope_theta=10000.0,
    rms_norm_eps=1e-6, dtype="bfloat16", output_head="embed",
    serving=dict(slots=4, kv_page=16, kv_pool_blocks=40,
                 prefill_buckets=[32, 64], prefill_batch_sizes=[1, 2],
                 prefill_chunk=32, max_new_tokens=32),
    check=dict(requests=12, min_tokens=40))


def _check(gap_max: float, gap_mean: float) -> dict:
    return dict(_BASE["check"], limits=dict(logit_gap_max=gap_max,
                                            logit_gap_mean=gap_mean))


# Limits from readings on the CPU at this size (8 virtual devices as the
# tests run, 8 seeds a family, 90-200 tokens compared a run; PR 24):
#   toy-dense  sound runs: widest gap <= 0.043, mean gap <= 0.0003;
#              float8 control: widest >= 0.24, mean >= 0.0115
#   toy-moe    sound runs: widest gap <= 0.29 (a router near-tie flips an
#              expert: it swings by seed), mean gap <= 0.0049;
#              float8 control: widest 0.49-1.9, mean >= 0.0307
# so the dense limits sit between on both numbers, and for the experts the
# mean gap is the number that separates (the widest gap's limit only keeps
# a gross fault out).
CONFIGS = {
    "toy-dense": dict(_BASE, family="dense", intermediate_size=128,
                      check=_check(0.1, 0.002)),
    "toy-moe": dict(_BASE, family="moe", intermediate_size=32,
                    num_experts=16, num_experts_per_tok=4,
                    check=_check(0.4, 0.012)),
}
MIXES = {
    "toy-open": dict(kind="open", rate_per_s=8.0, ramp_s=1, drain_s=10,
                     grid=8, schedule_seed=11,
                     prompt=dict(median=24, sigma=0.8, min=4, max=100),
                     output=dict(median=8, sigma=0.5, min=4, max=16)),
    "toy-sat": dict(kind="saturated", ahead=2, settle_s=0.5, ramp_stagger=3,
                    drain_s=0, grid=4, schedule_seed=12,
                    prompt=dict(median=40, sigma=0.5, min=8, max=120),
                    output=dict(median=16, sigma=0.3, min=8, max=32)),
}
CELLS = {"toy_moe_open": ("toy-moe", "toy-open"),
         "toy_dense_sat": ("toy-dense", "toy-sat")}
ADDED_METRIC = '''"""Added by a test: output tokens the window delivered."""

from vbench import stamps


def read(run):
    return stamps.window_tokens(run.records, 0.0, run.seconds)
'''


def build(root: str) -> dict:
    """Make the root under ``root``; returns the manifest written."""
    import jax

    shutil.copytree(os.path.join(REPO, "vbench"),
                    os.path.join(root, "vbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    for name, cfg in CONFIGS.items():
        path = f"vbench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        man["configs"].append(dict(name=name, source="tests", file=path,
                                   reduced=[], why="toy size for the CPU"))
    for name, mix in MIXES.items():
        with open(os.path.join(root, "vbench", "traffic",
                               f"{name}.json"), "w") as f:
            json.dump(mix, f)
    for name, (cfg, mix) in CELLS.items():
        man["workloads"].append(dict(name=name, config=cfg, traffic=mix,
                                     chips=1, why="toy cell for the CPU"))
    for group in ("end_to_end", "per_layer"):
        for m in man[group]:
            if "workloads" in m:
                m["workloads"] = m["workloads"] + list(CELLS)
    with open(os.path.join(root, "vbench", "metrics",
                           "toy_tokens_total.py"), "w") as f:
        f.write(ADDED_METRIC)
    man["per_layer"].append(dict(
        name="toy_tokens_total", unit="tokens", better="higher",
        source="host_clock", layer="load generator", moves="itl_mean_ms",
        workloads=list(CELLS)))
    kind = jax.devices()[0].device_kind
    with open(os.path.join(root, "vbench", "peaks", f"{kind}.json"),
              "w") as f:
        json.dump(dict(device_kind=kind, bf16_flops_per_s=1e12,
                       hbm_bytes_per_s=1e11, hbm_bytes=1e9,
                       source="made up for the CPU tests"), f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return man
