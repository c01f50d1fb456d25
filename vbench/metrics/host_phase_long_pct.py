"""Engine loop: share of the window lost in host phases judged long
(``long_ms`` of ``admission``, ``dispatch``, ``deliver``, ``swap_drain``
in ``stats()["tick_phase_ms"]``: samples of 1 s or more, and of 50 ms or
more on a pass that issued or followed no prefill launch, whole). A stop
of the machine that fell in one of them on such a pass reads here what
``host_pause_pct`` reads of it."""

from vbench import pauses
from vbench.rundata import HOST_PHASES


def read(run):
    return pauses.lost_pct(run, lambda s: pauses.long_ms(s, HOST_PHASES))
