"""The comparison that decides ``correct``.

Once the window has closed, the peak memory has been read and the engine
is freed, a sample of the requests the window served (the longest of them
and others drawn from the seed) goes once through the configuration's
plain reference: prompt and served tokens in, the logits at every position
that produced a served token out. The numbers compared are gaps, in
logits, by which the served token lies below the reference's best token at
its position: 0 where the program chose what the reference would.

Which input a served token was chosen under, and which row of it answers,
is a *pass*: ``tokens`` to embed, the ``rows`` whose logits answer, the
served token ``chosen`` at each and, for a family that asks, arrays
``beside`` the tokens (positions, block ids) that reach its ``layer``. A
program that serves one token a step is replayed by one causal pass a
request (token i against row i-1 of prompt + served[:-1]); a family whose
program commits several tokens of a stream in one pass, not in their
order, defines ``passes(cfg, prompt, served, trail)`` in its reference
module and rebuilds its own from the request's commit trail
(``Record.trail``: the pass, within the request, that committed each
served token).

The reference makes its own weights from the seed (vbench/weights.py), one
layer at a time in the model's order, each with the leaves of its own kind
where the family's layers are not all alike, and each sampled request
passes through that layer before the next is made: at most one layer's
float32 copy lives on the device.
"""

from __future__ import annotations

import collections
import importlib
import time

import numpy as np

from vbench import weights

_PAD = 512  # sequences are padded to a multiple: few shapes to compile
_ROWS = 128  # ... and so are the rows that go through the head


def pick_sample(records, seed: int, n: int, max_tokens: int = None) -> list:
    """The requests to compare: finished ones first (status OK), the
    longest always among them, the rest drawn from the seed; streams the
    window's end cut short stand in where too few finished. A request of
    more than ``max_tokens`` (prompt and served; a configuration's
    ``check.max_request_tokens``) is not drawn: the reference's time
    grows with the longest."""
    def size(r):
        return r.prompt_len + len(r.tokens)

    if max_tokens is not None:
        records = [r for r in records if size(r) <= max_tokens]
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


def _family(cfg: dict):
    return importlib.import_module(f"vbench.reference.{cfg['family']}")


def _checked(cfg: dict, p: dict) -> dict:
    """A family's pass as arrays, or ValueError: an index out of range
    would read another row in silence."""
    tokens, rows, chosen = (np.asarray(p[k], np.int32)
                            for k in ("tokens", "rows", "chosen"))
    beside = {k: np.asarray(v, np.int32)
              for k, v in p.get("beside", {}).items()}
    s = len(tokens)
    if (tokens.ndim != 1 or rows.ndim != 1 or rows.shape != chosen.shape
            or any(v.shape != (s,) for v in beside.values())):
        raise ValueError(
            f"a pass of {cfg['family']}: tokens {tokens.shape}, rows "
            f"{rows.shape}, chosen {chosen.shape}, beside "
            f"{ {k: v.shape for k, v in beside.items()} }")
    if (s and not 0 <= tokens.min() <= tokens.max() < cfg["vocab_size"]) \
            or (len(rows) and not 0 <= rows.min() <= rows.max() < s):
        raise ValueError(f"a pass of {cfg['family']} names a token outside "
                         f"the vocabulary or a row outside its {s}")
    return {"tokens": tokens, "rows": rows, "chosen": chosen,
            "beside": beside}


def replay(cfg: dict, prompt, served, trail=None) -> list:
    """The passes that answer one request's served tokens: the family's
    own, rebuilt from the commit trail, or the one causal pass of a
    program that serves a token a step (``beside`` None: its ``layer``
    takes no such argument)."""
    ref = _family(cfg)
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    if hasattr(ref, "passes"):
        return [_checked(cfg, p)
                for p in ref.passes(cfg, prompt, served, trail)]
    first = len(prompt) - 1
    return [{"tokens": np.concatenate([prompt, served[:-1]]),
             "rows": np.arange(first, first + len(served)),
             "chosen": served, "beside": None}]


def unanswered(served, passes: list) -> int:
    """Served tokens that no pass holds as ``chosen``, or more than one
    does. A pass names a token by its id, so they are counted by id."""
    want = collections.Counter(int(t) for t in served)
    got = collections.Counter(int(t) for p in passes for t in p["chosen"])
    return sum(abs(want[t] - got[t]) for t in want.keys() | got.keys())


def reference_logits(cfg: dict, seed: int, replays: list,
                     precision: str = "f32", log=None) -> list:
    """For each request's passes (``replay``): the reference's logits
    [rows, vocab] at the rows that answer, all passes of the request
    together in their order, computed under ``precision`` (see
    vbench/reference/common.py). A family with ``layer_kinds`` has one
    program a kind for making a layer and one for applying it, and its
    ``layer`` is told the kind; one with ``passes`` is handed, last, what
    the pass holds ``beside`` its tokens."""
    import jax
    import jax.numpy as jnp

    from vbench.reference import common

    ref = _family(cfg)
    specs = ref.weight_specs(cfg)
    key = weights.seed_key(seed)
    g = jax.jit(lambda k: weights.make_globals(k, specs))(key)
    kinds = weights.layer_kinds(ref, cfg)

    def programs(kind):
        """One layer of ``kind`` made, and one applied; only a family with
        ``layer_kinds`` has its ``layer`` told the kind."""
        also = () if kind is None else (kind,)
        return (jax.jit(lambda k, l: weights.make_layer(k, specs, l, kind)),
                jax.jit(lambda w, x, *beside: ref.layer(
                    cfg, w, x, precision, *also, *beside)))

    of_kind = {kind: programs(kind) for kind in set(kinds)}
    head = jax.jit(lambda g, x: common.head(cfg, g, x, precision))

    t0 = time.monotonic()
    xs, besides = [], []
    for p in (p for passes in replays for p in passes):
        pad = -len(p["tokens"]) % _PAD
        toks = np.concatenate([p["tokens"], np.zeros(pad, np.int32)])
        xs.append(g["embed"][jnp.asarray(toks)].astype(jnp.float32))
        besides.append(() if p["beside"] is None else (
            {k: jnp.asarray(np.concatenate([v, np.full(pad, -1, np.int32)]))
             for k, v in p["beside"].items()},))
    for l, kind in enumerate(kinds):
        make_layer, apply_layer = of_kind[kind]
        w = make_layer(key, l)
        xs = [apply_layer(w, x, *b) for x, b in zip(xs, besides)]
        del w
    out, walked = [], iter(xs)
    for passes in replays:
        rows = jnp.concatenate([next(walked)[jnp.asarray(p["rows"])]
                                for p in passes])
        n = rows.shape[0]
        rows = jnp.concatenate(
            [rows, jnp.zeros((-n % _ROWS, rows.shape[1]), rows.dtype)])
        out.append(np.asarray(head(g, rows))[:n])
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
    answering row of the same passes the token that the float8 reference
    puts first takes the served token's place, under the same names and
    limits, so the run has to come out as not correct. The program's own
    gaps then ride along as ``program_*``, beside no limit."""
    chk = cfg["check"]
    limits = chk["limits"]
    replayed = hasattr(_family(cfg), "passes")

    def trail_fits(r):  # a family's replay needs the pass of every token
        return not replayed or len(r.trail or ()) == len(r.tokens)

    sample = pick_sample([r for r in records if trail_fits(r)], seed,
                         chk["requests"], chk.get("max_request_tokens"))
    replays = [replay(cfg, r.prompt, r.tokens, r.trail) for r in sample]
    wrong_len = sum(1 for r in records
                    if r.status == "OK" and len(r.tokens) != r.max_new)
    bad_ids = sum(1 for r in records for t in r.tokens
                  if not 0 <= t < cfg["vocab_size"])
    n_tokens = sum(len(r.tokens) for r in sample)
    numbers = {
        "streams_wrong_length": {"value": wrong_len, "limit": 0},
        "tokens_outside_vocab": {"value": bad_ids, "limit": 0},
        "trail_wrong_length": {
            "value": sum(1 for r in records if not trail_fits(r)),
            "limit": 0},
        "tokens_unanswered": {
            "value": sum(unanswered(r.tokens, passes)
                         for r, passes in zip(sample, replays)),
            "limit": 0},
        "tokens_short_of_sample": {
            "value": max(0, chk["min_tokens"] - n_tokens), "limit": 0},
    }
    if not sample:
        return numbers
    ref = reference_logits(cfg, seed, replays, "f32", log)
    chosen = [np.concatenate([p["chosen"] for p in passes])
              for passes in replays]
    g = np.concatenate([gaps(l, c) for l, c in zip(ref, chosen)])
    if detail is not None:
        top2 = np.concatenate([np.sort(l, axis=-1)[:, -2:] for l in ref])
        detail["margin"] = [float(x) for x in top2[:, 1] - top2[:, 0]]
        detail["gap"] = [float(x) for x in g]
    if control:
        numbers["program_logit_gap_max"] = {"value": float(g.max()),
                                            "limit": None}
        numbers["program_logit_gap_mean"] = {"value": float(g.mean()),
                                             "limit": None}
        low = reference_logits(cfg, seed, replays, "fp8", log)
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
