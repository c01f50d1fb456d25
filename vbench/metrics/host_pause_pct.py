"""Engine loop: share of the window the serving process was paused, by its
own pause watch (``stats()["pauses"]["host"]["total_ms"]``: the summed
lateness of the watcher thread's wakes that came over 20 ms late). 0.0 in
a clean window; one stop of 0.108 s in 51 s reads 0.21."""

from vbench import pauses


def read(run):
    return pauses.lost_pct(run, lambda s: s["pauses"]["host"]["total_ms"])
