"""Where a run's pauses are: the benchmark's own entry point with three
listeners on, none of which the measured code can see.

- every collection of the interpreter's garbage collector that took more
  than a millisecond (``gc.callbacks``: generation, start, length);
- every phase of the engine's loop (``TickProfiler.phase``) with its name,
  start and length (the file holds all; standard error those over 45 ms
  that are no ``fetch``, and how many fetches were);
- the wake-ups of a thread of this process that sleeps 2 ms at a time (it
  needs the interpreter to go on), and of a second process that does the
  same and shares nothing with this one but the machine: a wake-up later
  than 20 ms is kept.

A pause that this process's sleeper sees and the other does not is the
interpreter's (a collection, or the thread that holds it taken off its
core); one that both see is the machine's; one that neither sees, inside
a ``fetch``, is the device's or the runtime's. All times are seconds of
``time.monotonic()``, which the processes of a machine share; the window's
start is kept with them. A cell's schedule is the same from run to run, so
two runs' phases line up by index. ``--compare 0`` skips the comparison
with the reference (the result line then says nothing of ``correct``).

    python hack/pause_probe.py --workload dsv2_longgen --seed <n> \\
        --seconds 51 --trace 0 [--compare 0]

The listeners' findings go to standard error and to
``chiprun_out/pause_probe_<seed>.json``.
"""

import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

PHASE_MS = 45  # longer than a step and its host work; a chunk's tick is a fetch

SLEEPER = """
import sys, time
last = time.monotonic()
while True:
    time.sleep(0.002)
    t = time.monotonic()
    if t - last > 0.020:
        print(round(last, 4), round(t - last, 4), flush=True)
    last = t
"""


def install(compare: bool):
    """Put the listeners on; returns what ``report`` needs."""
    other = subprocess.Popen([sys.executable, "-c", SLEEPER],
                             stdout=subprocess.PIPE, text=True,
                             env={"PATH": os.environ.get("PATH", "")})

    from vbench import check, run
    from vtpu.obs.tickprof import TickProfiler

    found = {"collections": [], "phases": [], "sleeper": [], "window": None}

    started = {}

    def on_gc(phase, info):
        if phase == "start":
            started["t"] = time.monotonic()
        else:
            t0 = started.get("t")
            dt = time.monotonic() - t0
            if dt > 0.001:
                found["collections"].append(
                    [round(t0, 4), round(dt, 4), info["generation"],
                     info["collected"]])

    gc.callbacks.append(on_gc)

    inner = TickProfiler.phase

    @contextlib.contextmanager
    def phase(self, name, ticks=1, **ids):
        t0 = time.monotonic()
        with inner(self, name, ticks=ticks, **ids):
            yield
        found["phases"].append([round(t0, 4),
                                round(time.monotonic() - t0, 4), name])

    TickProfiler.phase = phase

    def sleeper():
        last = time.monotonic()
        while True:
            time.sleep(0.002)
            t = time.monotonic()
            if t - last > 0.020:
                found["sleeper"].append([round(last, 4), round(t - last, 4)])
            last = t

    threading.Thread(target=sleeper, daemon=True).start()

    steady = run.wait_steady

    def wait_steady(eng, mix, slots):
        steady(eng, mix, slots)
        found["window"] = time.monotonic()
        found["objects"] = len(gc.get_objects())
        found["gc_counts"] = gc.get_count()
        found["gc_stats"] = gc.get_stats()

    run.wait_steady = wait_steady
    if not compare:
        check.compare = lambda *a, **k: {}

    return found, other


def report(found, other, seed) -> None:
    other.terminate()
    found["other_process"] = [
        [float(x) for x in line.split()]
        for line in other.stdout.read().splitlines()]
    found["gc_stats_end"] = gc.get_stats()
    w = found["window"] or 0.0

    def inside(rows):
        return [[round(r[0] - w, 3)] + r[1:] for r in rows
                if 0 <= r[0] - w <= 60]

    brief = {"seed": seed, "objects": found.get("objects"),
             "gc_counts": found.get("gc_counts"),
             "collections": [c for c in inside(found["collections"])
                             if c[1] > 0.005 or c[2] == 2],
             "phases": [p for p in inside(found["phases"])
                        if p[1] * 1e3 > PHASE_MS and p[2] != "fetch"],
             "fetches_over": sum(p[1] * 1e3 > PHASE_MS and p[2] == "fetch"
                                 for p in inside(found["phases"])),
             "sleeper": inside(found["sleeper"]),
             "other_process": inside(found["other_process"])}
    print("[probe] " + json.dumps(brief), file=sys.stderr, flush=True)
    path = os.path.join(ROOT, "chiprun_out", f"pause_probe_{seed}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(found, f)


def main() -> int:
    argv = sys.argv[1:]
    compare = True
    if "--compare" in argv:
        i = argv.index("--compare")
        compare = argv[i + 1] != "0"
        del argv[i:i + 2]
    seed = argv[argv.index("--seed") + 1]
    found, other = install(compare)
    from vbench import run

    rc = run.main(argv)
    report(found, other, seed)
    return rc


if __name__ == "__main__":
    sys.exit(main())
