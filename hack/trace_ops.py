"""A program's device time in a trace, a launch, by scope path and kind of
operation (own time): the table under ``python -m vbench.scopes``' top
twelve.

    python hack/trace_ops.py <trace dir or .xplane.pb> [jit_step]

Reads the newest ``.xplane.pb`` under the directory with vbench/scopes.py's
own reader; a scope path keeps the names of the vocabulary and the hybrid
family's ``ssm_*``; an operation's kind is its name without the number
(``fusion``, ``ssm_state_step``, ``reshape``).
"""

import bisect
import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from vbench import scopes  # noqa: E402


def main(argv) -> int:
    raw = scopes.load_xplane(scopes.newest_xplane(argv[0]))
    program = argv[1] if len(argv) > 1 else scopes.DECODE
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        launches = sum(scopes.module_key(m[0]) == program for m in modules)
        ms, count = collections.Counter(), collections.Counter()
        for op, own in scopes._own_time(dev["ops"]):
            i = bisect.bisect_right(starts, op[1]) - 1
            if (i < 0 or op[1] >= modules[i][1] + modules[i][2]
                    or scopes.module_key(modules[i][0]) != program):
                continue
            path = "/".join(
                part for part in op[3].rstrip(":").split("/")
                if part in scopes.VOCAB or part.startswith("ssm_"))
            key = (path, scopes.short_name(op[0]).split(".")[0])
            ms[key] += own / 1e9
            count[key] += 1
        print(f"{program}: {launches} launches")
        for key, total in ms.most_common(60):
            print(f"  {key[0]:32s} {key[1]:36s} {total / launches:9.4f} ms"
                  f"  {count[key] / launches:7.1f} ops a launch")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
