"""The saturated client hands the engine its backlog in the backlog's
order, whatever the threads' timing: a pool at its limit admits first
come, first served, so a swapped pair changes how many streams fit
(PERF.md section 6: 13 resident instead of 14 in one chip run of 18)."""

import threading
import time

import numpy as np
import pytest

from vbench.client import Client
from vbench.traffic import Planned


class _Request:
    status = "finished"
    t_depart_ns = 0

    def __init__(self, release):
        self._release = release

    def stream(self):
        self._release.wait(10)
        return iter(())


class _Engine:
    """submit() is slower the earlier the request, so threads that took
    their plans in order would reach it in the opposite one."""

    def __init__(self, n):
        self.n = n
        self.seen = []
        self.release = threading.Event()

    def submit(self, prompt, max_new_tokens=0):
        time.sleep(0.002 * max(0, self.n - len(prompt)))
        self.seen.append(len(prompt))
        return _Request(self.release)


class _Backlog:
    def __init__(self):
        self.index = 0

    def take(self):
        self.index += 1
        return Planned(self.index - 1, np.ones(self.index, np.int32), 4)


@pytest.mark.parametrize("outstanding", [2, 8, 18])
def test_saturated_submits_in_backlog_order(outstanding):
    eng = _Engine(outstanding)
    client = Client(eng)
    client.t0 = 0.0
    client.run_saturated(_Backlog(), outstanding)
    end = time.monotonic() + 10
    while len(eng.seen) < outstanding and time.monotonic() < end:
        time.sleep(0.005)
    client.close()
    eng.release.set()
    assert client.join(10)
    assert not client.errors
    assert eng.seen[:outstanding] == list(range(1, outstanding + 1))
    assert [r.index for r in client.records()][:outstanding] == list(
        range(outstanding))
