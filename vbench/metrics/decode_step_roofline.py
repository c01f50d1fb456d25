"""Kernels: the least time the chip could take for one decode step over
the device time one took, in percent.

The decode steps are found in the trace by count: the engine's counter
says how many decode ticks it dispatched while the trace ran, and the
``jit_step`` programs are taken most-launched first until their launches
cover that count (one program a read window; the engine's admission step
carries the same name and launches less often). The least time is the
larger of FLOPs over the peak rate and bytes over the peak bandwidth, both
from the configuration's shapes for the streams and cached tokens live in
the traced part: weights read once, live tokens only.
"""

from vbench import stamps, trace

SLACK = 2  # ticks the trace's edges may cut


def read(run):
    t = run.trace
    if not t or not run.trace_stats:
        return None
    before, after = run.trace_stats
    ticks = after["decode_ticks"] - before["decode_ticks"]
    steps = sorted((v for k, v in t["modules"].items()
                    if trace.module_key(k) == "jit_step"),
                   key=lambda v: -v[0])
    launches, seconds = 0, 0.0
    for count, secs in steps:
        if launches >= ticks - SLACK:
            break
        launches, seconds = launches + count, seconds + secs
    if ticks < 2 or launches < ticks - SLACK or seconds <= 0:
        return None
    a, b = run.trace_span
    n = 8
    live = [stamps.live_tokens_at(run.records, a + (b - a) * (i + 0.5) / n)
            for i in range(n)]
    batch = sum(s for s, _ in live) / n
    tokens = sum(tk for _, tk in live) / n
    if batch < 1:
        return None
    flops, byts = run.step_cost(run.cfg, batch, tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / launches)
