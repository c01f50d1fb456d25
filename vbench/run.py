"""One run of one cell.

    python -m vbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json and everything else by the names it
gives: vbench/configs/<config>.json, vbench/traffic/<mix>.json,
vbench/metrics/<metric>.py. Makes the weights from the seed, builds the
program's ``ServingEngine`` in this process, warms it and brings the mix to
its steady state (all of that is ``setup_s``), measures for ``--seconds``,
frees the engine, and holds a sample of what the window served against the
plain reference. The last line of standard output is the result.

Two further options are the builder's, and no check uses them.
``--control 1`` puts the reference computed in float8 in the program's
place in that comparison, under the same names and limits: such a run has
to print ``"correct": false``. ``--out <file>`` writes the run's details
(the trace's programs, every compared position) as JSON.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # before the heavy imports: set-up counts them

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from vbench import check, manifest, trace, traffic, weights  # noqa: E402
from vbench.client import Client  # noqa: E402
from vbench.rundata import Run  # noqa: E402

TRACE_AT, TRACE_S = 0.4, 5.0   # the traced part: from 40 % of the window
RAMP_LIMIT_S = 150.0           # a saturated mix not steady by then fails
WARM_PROMPT, WARM_TOKENS = 8, 2


class RunFailed(Exception):
    """The run cannot give a result; exit non-zero, print none."""


def say(what: str, **info) -> None:
    print(f"[vbench] {what} " + json.dumps(info, default=str),
          file=sys.stderr, flush=True)


def now() -> float:
    return time.monotonic_ns() / 1e9


class CompileCounter:
    """Counts the programs JAX compiles or loads from its cache while
    ``on`` (a jax.monitoring listener: silent, unlike jax_log_compiles).
    Nothing may compile inside the window."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    _instance = None

    def __init__(self):
        import jax

        self.on = False
        self.seen: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._note)

    @classmethod
    def fresh(cls) -> "CompileCounter":
        """The process's one listener, emptied (a listener cannot be taken
        off again, so runs in one process share it)."""
        if cls._instance is None:
            cls._instance = cls()
        cls._instance.on, cls._instance.seen = False, []
        return cls._instance

    def _note(self, event: str, duration: float, **kw) -> None:
        if self.on and event in self.EVENTS:
            self.seen.append(f"{event.rsplit('/', 1)[-1]}:{duration:.3f}s")


def find_devices(chips: int):
    """The accelerator this cell needs, or RunFailed: there is no CPU mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunFailed(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise RunFailed(f"the cell asks for {chips} chips, JAX sees "
                        f"{len(devs)}")
    return devs


def place_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path
    (vtpu/util/jaxcache.py: JAX_COMPILATION_CACHE_DIR if set, else
    <checkout>/.jax_cache), holding every program however small."""
    import jax
    from vtpu.util.jaxcache import place_compile_cache

    path = place_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def build_engine(cfg: dict, seed: int):
    """Weights from the seed, in one jitted call on the device; the
    program's engine over them, started and warmed: one short request
    served to its end proves every executable of the loop is in."""
    import jax
    import numpy as np

    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    sut = importlib.import_module(f"vbench.sut.{cfg['family']}")
    t = now()
    w = weights.make_all(seed, ref.weight_specs(cfg),
                         cfg["num_hidden_layers"],
                         weights.layer_kinds(ref, cfg))
    jax.block_until_ready(w)
    t_weights = now() - t
    t = now()
    eng = sut.build(cfg, w)
    del w
    eng.start()
    req = eng.submit(np.arange(1, 1 + WARM_PROMPT, dtype=np.int32),
                     max_new_tokens=WARM_TOKENS)
    got = list(req.stream())
    if len(got) != WARM_TOKENS or req.status != "OK":
        raise RunFailed(f"warm-up request ended {req.status} with "
                        f"{len(got)} tokens: {eng.stats()['loop_error']}")
    say("engine", weights_s=round(t_weights, 2),
        build_and_warm_s=round(now() - t, 2))
    return eng, ref


def wait_steady(eng, mix: dict, slots: int) -> None:
    """A saturated mix is steady once the engine has first filled its
    slots or been refused by its pool, and ``settle_s`` more have passed."""
    base = eng.stats()["pool_blocked_admissions"]
    end = now() + RAMP_LIMIT_S
    while now() < end:
        s = eng.stats()
        if s["loop_error"]:
            raise RunFailed(f"engine loop died: {s['loop_error']}")
        if (s["active_slots"] + s["admitting_slots"] >= slots
                or s["pool_blocked_admissions"] > base):
            time.sleep(mix["settle_s"])
            return
        time.sleep(0.05)
    raise RunFailed(f"not steady after {RAMP_LIMIT_S:.0f}s: {eng.stats()}")


def sleep_until(t: float) -> None:
    d = t - now()
    if d > 0:
        time.sleep(d)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, control: bool = False, t_start: float = None,
             devices=None, out: dict = None) -> dict:
    """Everything after the look for a chip; returns the result line as a
    dict. ``devices`` is what jax.devices() gave."""
    import jax

    t_start = now() if t_start is None else t_start
    devices = devices or jax.devices()
    man = manifest.load(root)
    cell = manifest.cell(man, workload)
    cfg = manifest.config(man, root, cell["config"])
    mix = traffic.load_mix(cell["traffic"], root)
    peaks = manifest.peaks(root, devices[0].device_kind) if traced else {}
    group = "per_layer" if traced else "end_to_end"
    wanted = manifest.metrics_of(man, group, workload)
    readers = {m["name"]: manifest.reader(root, m["name"]) for m in wanted}

    say("start", imports_and_device_s=round(now() - t_start, 2))
    eng, ref = build_engine(cfg, seed)
    slots = cfg["serving"]["slots"]
    counter = CompileCounter.fresh()
    client = Client(eng)
    vocab = cfg["vocab_size"]
    try:
        if mix["kind"] == "open":
            t0 = now() + mix["ramp_s"]
            schedule = traffic.open_schedule(mix, seed, vocab, seconds)
            sender = client.run_open(schedule, t0)
            sleep_until(t0)
        else:
            sender = None
            client.run_saturated(traffic.backlog(mix, seed, vocab),
                                 slots + mix["ahead"])
            wait_steady(eng, mix, slots)
            t0 = now()
            client.t0 = t0
        counter.on = True
        stats0 = eng.stats()
        setup_s = t0 - t_start
        say("window_open", setup_s=round(setup_s, 3))
        trace_span = trace_stats = reduced = None
        if traced:
            trace_dir = os.path.join(root, ".vbench_out", "trace", workload)
            shutil.rmtree(trace_dir, ignore_errors=True)
            length = min(TRACE_S, seconds / 3)
            sleep_until(t0 + TRACE_AT * seconds)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            sa, ta = eng.stats(), now()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            sleep_until(ta + length)
            tb, sb = now(), eng.stats()
            jax.profiler.stop_trace()
            trace_span, trace_stats = (ta - t0, tb - t0), (sa, sb)
        sleep_until(t0 + seconds)
        stats1 = eng.stats()
        t1 = now()
        counter.on = False
        client.close()
        if sender is not None:
            sender.join(timeout=5)
            end = t1 + mix["drain_s"]
            while client.first_tokens_owed(t1) and now() < end:
                time.sleep(0.02)
        give_up = now()
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell["chips"]])
        if stats1["loop_error"]:
            raise RunFailed(f"engine loop died: {stats1['loop_error']}")
    finally:
        t_stop = now()
        eng.stop()
        joined = client.join(30.0)
    if client.errors or not joined:
        raise RunFailed(f"client threads: {client.errors or 'did not end'}")
    records = client.records()
    del eng, client
    gc.collect()
    jax.clear_caches()

    if traced:
        loaded = trace.load_xplane(trace.find_xplane(trace_dir))
        reduced = trace.reduce(loaded)
        if reduced["busy_s"] <= 0:
            raise RunFailed("no operation ran on the device in the trace")
    run = Run(records=records, seconds=seconds, setup_s=setup_s,
              give_up_s=give_up - t0, stats0=stats0, stats1=stats1, cfg=cfg,
              mix=mix, peaks=peaks, step_cost=ref.decode_step_cost,
              trace=reduced, trace_span=trace_span, trace_stats=trace_stats)
    units = {m["name"]: m["unit"] for m in wanted}
    metrics = {}
    for name, read in readers.items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}

    def ended_badly(r):  # a terminal other than OK, before the engine's stop
        return r.status not in (None, "OK") and r.ended_s < t_stop - t0

    if mix["kind"] == "open":  # every request due in the window
        tried = [r for r in records if r.in_window]
        failed = [r for r in tried if not r.stamps or ended_badly(r)]
    else:  # every request in service at some time of the window
        tried = [r for r in records if r.sent_s < seconds
                 and not (r.status is not None and r.ended_s < 0)]
        failed = [r for r in tried if ended_badly(r)]

    t = now()
    detail = {} if out is not None else None
    numbers = check.compare(cfg, seed, records, control=control, log=say,
                            detail=detail)
    correct = check.verdict(numbers)
    say("compared", seconds=round(now() - t, 2),
        compiles_in_window=len(counter.seen), window_compiles=counter.seen[:8])
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(tried),
              "failed": len(failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = numbers
    if out is not None:
        out.update({"modules": reduced["modules"] if reduced else None,
                    "positions": detail,
                    "trace_cut": trace.cut(loaded) if traced else None,
                    "stats1": stats1, "trace_span": trace_span,
                    "compiles_in_window": counter.seen,
                    "requests": [[r.index, r.prompt_len, r.max_new,
                                  len(r.tokens), r.status,
                                  round(r.due_s, 4), round(r.sent_s, 4),
                                  (round(r.stamps[0], 4) if r.stamps else None),
                                  round(r.ended_s, 4), r.trail]
                                 for r in records]})
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    out = {} if args.out else None
    try:
        if not os.path.isdir(os.path.join(ROOT, "vtpu")):
            raise RunFailed(f"no program here: {ROOT}/vtpu is missing")
        place_cache()
        devices = find_devices(
            manifest.cell(manifest.load(ROOT), args.workload)["chips"])
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), control=bool(args.control),
                          t_start=_T_START, devices=devices, out=out)
    except RunFailed as e:
        print(f"vbench: {e}", file=sys.stderr)
        return 1
    if out is not None:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"result": result, **out}, f)
    for name, n in result["compared"].items():
        print(f"compared {name} = {n['value']} (limit {n['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
