"""Engine loop: share of the window inside the interpreter's collections,
all three generations (``stats()["pauses"]["gc"][g]["total_ms"]``, timed
by the program's ``gc.callbacks`` listener)."""

from vbench import pauses


def read(run):
    return pauses.lost_pct(run, lambda s: sum(
        s["pauses"]["gc"][g]["total_ms"] for g in ("0", "1", "2")))
