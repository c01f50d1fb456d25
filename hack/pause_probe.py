"""Does the program see every stop? What it cannot do itself is stand
outside: a second process that shares nothing with the run but the machine
sleeps 2 ms at a time and keeps each wake over 20 ms late. This script
starts it, runs the plain benchmark command beside it with ``--out``, and
prints each such wake inside the window beside the rows that cover it in
the program's own records, ``stats1["pauses"]["recent"]["host"]``
(vtpu/obs/pauses.py) and ``stats1["tick_long"]`` (vtpu/obs/tickprof.py),
or "unseen". Nothing is patched, nothing listens inside the measured
process. Times are seconds of ``time.monotonic()``, which the processes of
a machine share, from the window's start (stamped as the run's
``window_open`` line is read); a row's start comes first.

    python hack/pause_probe.py --workload dsv2_longgen --seed <n> \\
        --seconds 51 --trace 0

The findings are one line on standard error after the run's own; the run's
``--out`` file is ``chiprun_out/pause_probe_<seed>.json``.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLEEPER = """
import time
last = time.monotonic()
while True:
    time.sleep(0.002)
    t = time.monotonic()
    if t - last > 0.020:
        print(last, t - last, flush=True)
    last = t
"""


def covering(rows, i, opened, t0, t1):
    """The rows (start_ns at ``i``, milliseconds after it) that meet
    [t0, t1], the start first and in seconds from ``opened``."""
    at = [(r[i] / 1e9 - opened, r) for r in rows]
    return [[round(a, 4)] + r[:i] + r[i + 1:] for a, r in at
            if a <= t1 and a + r[i + 1] / 1e3 >= t0]


def report(stats: dict, wakes: list, opened: float, seconds: float) -> dict:
    host = stats.get("pauses", {}).get("recent", {}).get("host", [])
    long = stats.get("tick_long", [])
    seen = covering(host, 0, opened, 0.0, seconds)
    found = {"pauses_ms": round(sum(r[1] for r in seen), 3), "pauses": seen,
             "tick_long": covering(long, 2, opened, 0.0, seconds),
             "stops": []}
    for t0, length in ((s - opened, n) for s, n in wakes):
        if 0.0 <= t0 <= seconds:
            found["stops"].append({
                "other": [round(t0, 4), round(length * 1e3, 1)],
                "pauses": covering(host, 0, opened, t0, t0 + length)
                or "unseen",
                "tick_long": covering(long, 2, opened, t0, t0 + length)
                or "unseen"})
    return found


def main() -> int:
    argv = sys.argv[1:]
    seed = argv[argv.index("--seed") + 1]
    out = os.path.join(ROOT, "chiprun_out", f"pause_probe_{seed}.json")
    other = subprocess.Popen([sys.executable, "-c", SLEEPER],
                             stdout=subprocess.PIPE, text=True,
                             env={"PATH": os.environ.get("PATH", "")})
    run = subprocess.Popen(
        [sys.executable, "-m", "vbench.run", *argv, "--out", out],
        cwd=ROOT, stderr=subprocess.PIPE, text=True)
    opened = None
    for line in run.stderr:
        if opened is None and "window_open" in line:
            opened = time.monotonic()
        sys.stderr.write(line)
    rc = run.wait()
    other.terminate()
    wakes = [[float(x) for x in line.split()]
             for line in other.stdout.read().splitlines()]
    if rc == 0 and opened is not None:
        with open(out) as f:
            stats = json.load(f)["stats1"]
        found = report(stats, wakes, opened,
                       float(argv[argv.index("--seconds") + 1]))
        print("[probe] " + json.dumps({"seed": seed, **found}),
              file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
