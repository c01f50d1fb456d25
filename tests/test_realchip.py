"""Opt-in real-hardware proof: libvtpu wrapping the real PJRT plugin.

Gated behind VTPU_REALCHIP=1 because it needs a live TPU attachment; CI runs
the same wrapper against fake_pjrt.cc (tests/test_libvtpu.py). The proof
itself (hack/realchip_proof.py) asserts workload correctness, tagged
over-cap rejection with tenant survival, and live shared-region usage —
the vTPU analog of reference test/e2e/pod/test_pod.go:85-120.
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.skipif(
    not os.environ.get("VTPU_REALCHIP"),
    reason="opt-in: set VTPU_REALCHIP=1 with a live TPU attachment",
)
def test_realchip_proof():
    # conftest pins this process to the CPU; the proof's child needs the
    # chip, so it must not inherit that choice
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    r = subprocess.run(
        [sys.executable, str(REPO / "hack" / "realchip_proof.py")],
        capture_output=True, text=True, timeout=580, env=env,
    )
    assert r.returncode == 0, f"realchip proof failed:\n{r.stdout}\n{r.stderr}"
