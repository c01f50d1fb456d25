"""The saturated client hands the engine its backlog in the backlog's
order, whatever the threads' timing: a pool at its limit admits first
come, first served, so a swapped pair changes how many streams fit
(PERF.md section 6: 13 resident instead of 14 in one chip run of 18).
Once ``close()`` has returned no worker submits again, so the engine may
be stopped; and a program's commit trail (``Request.trail``) reaches the
client's ``Record`` through either loop."""

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


class _Stopping(_Engine):
    """submit() takes a while, and fails once the engine is stopped."""

    stopped = False

    def submit(self, prompt, max_new_tokens=0):
        if self.stopped:
            raise RuntimeError("ServingEngine is stopped")
        self.seen.append(len(prompt))
        time.sleep(0.05)
        return _Request(self.release)


def test_no_worker_submits_once_close_has_returned():
    """One worker is inside submit() and holds the order, the other has
    passed its look at ``_closing`` and waits for it: close() has to wait
    for the first and turn the second back."""
    eng = _Stopping(2)
    eng.release.set()          # a stream ends at once: workers come again
    client = Client(eng)
    client.t0 = 0.0
    client.run_saturated(_Backlog(), 2)
    end = time.monotonic() + 10
    while not eng.seen and time.monotonic() < end:
        time.sleep(0.001)
    time.sleep(0.01)
    client.close()
    eng.stopped = True
    assert client.join(10)
    assert client.errors == []


class _Trailed:
    """A request whose program commits its four tokens in two passes, not
    in their order."""

    status = "OK"
    t_depart_ns = 0
    trail = (1, 0, 0, 1)

    def stream(self):
        return iter((7, 8, 9, 10))


class _Untrailed(_Trailed):
    trail = property()      # no such attribute, as every program of today


class _Serving:
    def __init__(self, request):
        self.request = request

    def submit(self, prompt, max_new_tokens=0):
        return self.request()


@pytest.mark.parametrize("loop", ["saturated", "open"])
@pytest.mark.parametrize("request_type, trail", [
    (_Trailed, [1, 0, 0, 1]), (_Untrailed, None)])
def test_a_requests_trail_reaches_its_record(loop, request_type, trail):
    client = Client(_Serving(request_type))
    if loop == "open":
        plans = [Planned(i, np.ones(3, np.int32), 4, due_s=0.0)
                 for i in range(3)]
        client.run_open(plans, time.monotonic()).join(10)
    else:
        client.t0 = 0.0
        client.run_saturated(_Backlog(), 2)
        time.sleep(0.05)
    client.close()
    assert client.join(10) and not client.errors
    records = client.records()
    assert len(records) >= 3
    for r in records:
        assert r.tokens == [7, 8, 9, 10] and r.request is None
        assert r.trail == trail
