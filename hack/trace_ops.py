"""A program's device time in a trace, a launch, by scope path and kind of
operation (own time): the table under ``python -m vbench.scopes``' top
twelve.

    python hack/trace_ops.py <trace dir or .xplane.pb> [jit_step] [each]

``each``: beside the table, one line a compiled program of that name (its
id: a family's chunk programs differ by read window), with its launches,
a launch's milliseconds and the innermost scope's share of them: what a
mean over all launches hides when two traces hold different mixes.

Reads the newest ``.xplane.pb`` under the directory with vbench/scopes.py's
own reader; a scope path keeps the names of the vocabulary, the hybrid
family's ``ssm_*``, the latent family's (``vbench.latent_scopes.NAMES``) and
a chunk's attention over its window (``vbench.chunk_scopes.NAMES``);
an operation's kind is its name without the number
(``fusion``, ``ssm_state_step``, ``reshape``).
"""

import bisect
import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from vbench import chunk_scopes, latent_scopes, scopes  # noqa: E402


def main(argv) -> int:
    raw = scopes.load_xplane(scopes.newest_xplane(argv[0]))
    program = argv[1] if len(argv) > 1 else scopes.DECODE
    for dev in raw["devices"].values():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        launches = sum(scopes.module_key(m[0]) == program for m in modules)
        ms, count = collections.Counter(), collections.Counter()
        by_id = collections.defaultdict(collections.Counter)
        for op, own in scopes._own_time(dev["ops"]):
            i = bisect.bisect_right(starts, op[1]) - 1
            if (i < 0 or op[1] >= modules[i][1] + modules[i][2]
                    or scopes.module_key(modules[i][0]) != program):
                continue
            names = [part for part in op[3].rstrip(":").split("/")
                     if part in scopes.VOCAB or part.startswith("ssm_")
                     or part in latent_scopes.NAMES
                     or part in chunk_scopes.NAMES]
            key = ("/".join(names), scopes.short_name(op[0]).split(".")[0])
            ms[key] += own / 1e9
            count[key] += 1
            by_id[modules[i][0]][names[-1] if names else ""] += own / 1e9
        print(f"{program}: {launches} launches")
        for key, total in ms.most_common(60):
            print(f"  {key[0]:32s} {key[1]:36s} {total / launches:9.4f} ms"
                  f"  {count[key] / launches:7.1f} ops a launch")
        for name in sorted(by_id) if "each" in argv[2:] else ():
            n = sum(m[0] == name for m in modules)
            whole = sum(m[2] for m in modules if m[0] == name) / 1e9 / n
            parts = "  ".join(f"{scope or 'unscoped'} {total / n:.3f}"
                              for scope, total in by_id[name].most_common(8))
            print(f"{name}: {n} launches, {whole:.3f} ms a launch: {parts}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
