"""Hermeticity lock for the driver's multi-chip dryrun.

Round 1's run failed because eager ops inside ``dryrun_multichip`` dispatched
to the ambient default platform — a wedged TPU client in the driver env whose
first executed op raised. The fix pins ``jax_default_device`` to the resolved
dryrun mesh for the whole body. These tests lock the property in: the second
test breaks eager dispatch for any op that would consult the *unpinned*
ambient platform (exactly the driver failure mode) and asserts the dryrun
still completes.
"""

import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import __graft_entry__ as graft  # noqa: E402

# Heavyweight tier: compile-bound, tens of seconds
# each; CI runs them separately so the unit tier stays under two minutes.
pytestmark = pytest.mark.slow


def test_dryrun_multichip_cpu_mesh():
    prev = jax.config.jax_default_device
    graft.dryrun_multichip(8)
    assert jax.config.jax_default_device is prev  # restored after the run


def test_dryrun_hermetic_to_wedged_default_platform(monkeypatch):
    """Simulate round 1's driver env: any eager primitive that runs
    while jax_default_device is unpinned explodes (as the wedged TPU client
    did). The dryrun must pin every eager op to its own mesh and pass."""
    from jax._src import core as jcore

    real = jcore.EvalTrace.process_primitive

    def wedged(self, primitive, *rest, **kw):
        if jax.config.jax_default_device is None:
            raise RuntimeError(
                f"simulated wedged default platform: eager {primitive} "
                "dispatched without a pinned default device"
            )
        return real(self, primitive, *rest, **kw)

    prev = jax.config.jax_default_device
    monkeypatch.setattr(jcore.EvalTrace, "process_primitive", wedged)
    graft.dryrun_multichip(8)
    assert jax.config.jax_default_device is prev


def test_dryrun_refuses_fewer_devices_than_asked(monkeypatch):
    """A default platform narrower than the mesh asked for (the one-chip
    TPU beside an 8-wide CPU client) is an error naming the launch flags —
    the dryrun never moves to another platform by itself."""
    real_devices = jax.devices

    def narrow(platform=None):
        if platform is None:
            return real_devices()[:1]
        return real_devices(platform)

    monkeypatch.setattr(jax, "devices", narrow)
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        graft._devices_for_dryrun(8)
