"""The comparison that decides ``correct``.

Once the window has closed, the peak memory has been read and the engine
is freed, a sample of the requests the window served (the longest of them
and others drawn from the seed) goes once through the configuration's
plain reference: prompt and served tokens in, the logits at every position
that produced a served token out. The numbers compared are gaps, in
logits, by which the served token lies below the reference's best token at
its position: 0 where the program chose what the reference would.

The reference makes its own weights from the seed (vbench/weights.py), one
layer at a time, and each sampled request passes through that layer before
the next is made: at most one layer's float32 copy lives on the device.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from vbench import weights

_PAD = 512  # sequences are padded to a multiple: few shapes to compile
_ROWS = 128  # ... and so are the rows that go through the head


def pick_sample(records, seed: int, n: int) -> list:
    """The requests to compare: finished ones first (status OK), the
    longest always among them, the rest drawn from the seed; streams the
    window's end cut short stand in where too few finished."""
    def size(r):
        return r.prompt_len + len(r.tokens)

    done = [r for r in records if r.status == "OK" and r.tokens]
    cut = [r for r in records
           if r.status != "OK" and len(r.tokens) >= 16]
    pool = done if len(done) >= n else done + sorted(
        cut, key=size, reverse=True)[:n - len(done)]
    if not pool:
        return []
    pool = sorted(pool, key=lambda r: r.index)
    longest = max(pool, key=size)
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[int(i)] for i in take]


def reference_logits(cfg: dict, seed: int, samples: list,
                     precision: str = "f32", log=None) -> list:
    """For each (prompt, served) of ``samples``: the reference's logits
    [len(served), vocab] at the positions that produced the served tokens,
    computed under ``precision`` (see vbench/reference/common.py)."""
    import jax
    import jax.numpy as jnp

    from vbench.reference import common

    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    specs = ref.weight_specs(cfg)
    key = weights.seed_key(seed)
    g = jax.jit(lambda k: weights.make_globals(k, specs))(key)
    make_layer = jax.jit(lambda k, l: weights.make_layer(k, specs, l))
    apply_layer = jax.jit(lambda w, x: ref.layer(cfg, w, x, precision))
    head = jax.jit(lambda g, x: common.head(cfg, g, x, precision))

    t0 = time.monotonic()
    xs, spans = [], []
    for prompt, served in samples:
        toks = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(served[:-1], np.int32)])
        pad = -len(toks) % _PAD
        toks = np.concatenate([toks, np.zeros(pad, np.int32)])
        xs.append(g["embed"][jnp.asarray(toks)].astype(jnp.float32))
        spans.append((len(prompt) - 1, len(prompt) - 1 + len(served)))
    for l in range(cfg["num_hidden_layers"]):
        w = make_layer(key, l)
        xs = [apply_layer(w, x) for x in xs]
        del w
    out = []
    for x, (a, b) in zip(xs, spans):
        rows = x[a:b]
        pad = -rows.shape[0] % _ROWS
        rows = jnp.concatenate(
            [rows, jnp.zeros((pad, rows.shape[1]), rows.dtype)])
        out.append(np.asarray(head(g, rows))[:b - a])
    if log is not None:
        log("reference", precision=precision, seconds=round(
            time.monotonic() - t0, 2), padded=[int(x.shape[0]) for x in xs])
    return out


def gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """By how much each chosen token's reference logit lies below the
    reference's best at its position (>= 0)."""
    best = ref_logits.max(axis=-1)
    got = ref_logits[np.arange(len(chosen)), np.asarray(chosen)]
    return best - got


def compare(cfg: dict, seed: int, records: list, control: bool = False,
            log=None, detail: dict = None) -> dict:
    """Every number compared, each beside its limit: {name: {"value",
    "limit"}} (correct where value <= limit for all). With ``control`` the
    lower-precision reference stands in the program's place: at every
    compared position the token that the float8 reference puts first
    takes the served token's place, under the same names and limits, so
    the run has to come out as not correct. The program's own gaps then
    ride along as ``program_*``, beside no limit."""
    chk = cfg["check"]
    limits = chk["limits"]
    sample = pick_sample(records, seed, chk["requests"])
    pairs = [(r.prompt, r.tokens) for r in sample]
    wrong_len = sum(1 for r in records
                    if r.status == "OK" and len(r.tokens) != r.max_new)
    bad_ids = sum(1 for r in records for t in r.tokens
                  if not 0 <= t < cfg["vocab_size"])
    numbers = {
        "streams_wrong_length": {"value": wrong_len, "limit": 0},
        "tokens_outside_vocab": {"value": bad_ids, "limit": 0},
    }
    n_tokens = sum(len(s) for _, s in pairs)
    numbers["tokens_short_of_sample"] = {
        "value": max(0, chk["min_tokens"] - n_tokens), "limit": 0}
    if not pairs:
        return numbers
    ref = reference_logits(cfg, seed, pairs, "f32", log)
    g = np.concatenate([gaps(l, s) for l, (_, s) in zip(ref, pairs)])
    if detail is not None:
        top2 = np.concatenate([np.sort(l, axis=-1)[:, -2:] for l in ref])
        detail["margin"] = [float(x) for x in top2[:, 1] - top2[:, 0]]
        detail["gap"] = [float(x) for x in g]
    if control:
        numbers["program_logit_gap_max"] = {"value": float(g.max()),
                                            "limit": None}
        numbers["program_logit_gap_mean"] = {"value": float(g.mean()),
                                             "limit": None}
        low = reference_logits(cfg, seed, pairs, "fp8", log)
        g = np.concatenate([gaps(l, lo.argmax(-1))
                            for l, lo in zip(ref, low)])
        if detail is not None:
            detail["control_gap"] = [float(x) for x in g]
    # a configuration's limits name the numbers it is held to; the other
    # is still printed, beside no limit
    numbers["logit_gap_max"] = {"value": float(g.max()),
                                "limit": limits.get("logit_gap_max")}
    numbers["logit_gap_mean"] = {"value": float(g.mean()),
                                 "limit": limits.get("logit_gap_mean")}
    numbers["tokens_compared"] = {"value": int(n_tokens), "limit": None}
    return numbers


def verdict(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values()
               if n["limit"] is not None)
