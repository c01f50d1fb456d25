"""QoS priority-gate BENEFIT, measured where same-chip co-tenancy is
constructible.

A real chip belongs to one process at a time under the installed libtpu (a
second client fails at start-up; measured in PR 21), so the reference's
benefit scenario — a high tenant recovering its solo latency when the
monitor gates a co-located low tenant (cmd/vGPUmonitor/feedback.go:75-135)
— cannot be produced by two processes on one real chip. It IS
constructible one layer down: the
fake PJRT plugin's FAKE_PJRT_SHARED_QUEUE backs its serial busy-queue with
an mmap'd file, so two PROCESSES (real libvtpu shims, real regions, the
real monitor binary's feedback loop) contend on one emulated chip with
deterministic 100 ms kernels.

Phases (same binary stack as production: pjrt_smoke -> libvtpu.so ->
fake_pjrt.so, python -m vtpu.monitor):
  solo       H alone: per-exec wall ~ exec_ns
  contended  L (priority 0) saturates the shared queue; H degrades ~2x
  protected  + the monitor binary: census sees H active, gates L
             (recent_kernel=-1 -> libvtpu's execute gate), H returns to solo

Criteria: contended - solo >= 10% (engineered:
expect ~2x), protected within ~10% of solo (scheduling jitter on a shared
CPU host is the noise floor here), low tenant demonstrably gated.

Writes build/QOS_BENEFIT.json.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
LIB = REPO / "libvtpu" / "build"
HOOK = REPO / "build" / "qos_benefit_hook"
EXEC_NS = 100_000_000  # 100 ms kernels: >> scheduling jitter, quick phases
H_EXECS = 60
MONITOR_PORT = 19397


def tenant_env(name: str, priority: int, shared_queue: pathlib.Path) -> dict:
    cdir = HOOK / "containers" / f"pod{name}_main"
    cdir.mkdir(parents=True, exist_ok=True)
    (cdir / "chips").write_text("fakechip-0")
    env = dict(os.environ)
    env.update({
        # the shim registers a region device slot per TPU_DEVICE_MEMORY_LIMIT
        # entry ("device-0"); the census aggregates priorities by that uuid,
        # so the limit env is what makes the two tenants co-located
        "TPU_DEVICE_MEMORY_LIMIT_0": "4g",
        "VTPU_REAL_LIBTPU": str(LIB / "fake_pjrt.so"),
        "FAKE_PJRT_SHARED_QUEUE": str(shared_queue),
        "FAKE_PJRT_EXEC_NS": str(EXEC_NS),
        "PJRT_SMOKE_D2H": "1",  # completion-coupled: queue wait is visible
        "VTPU_TASK_PRIORITY": str(priority),
        "VTPU_SHARED_REGION": str(cdir / "usage.cache"),
    })
    return env


def run_smoke(env: dict, execs: int, timeout: float = 300) -> dict:
    r = subprocess.run(
        [str(LIB / "pjrt_smoke"), str(LIB / "libvtpu.so"), "1", "1", str(execs)],
        env=env, capture_output=True, text=True, timeout=timeout)
    lines = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    assert lines, f"no RESULT (rc={r.returncode}): {r.stderr[-400:]}"
    return json.loads(lines[-1][7:])


def start_low(env: dict, execs: int = 3000):
    return subprocess.Popen(
        [str(LIB / "pjrt_smoke"), str(LIB / "libvtpu.so"), "1", "1", str(execs)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def start_monitor():
    (HOOK / "chips.json").write_text(json.dumps([{
        "uuid": "fakechip-0", "index": 0, "devmem_mb": 16384, "devcore": 100,
        "type": "TPU-v5e", "numa": 0, "healthy": True, "mode": "",
    }]))
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    logf = open(HOOK / "monitor.log", "w")  # file, never an undrained pipe
    return subprocess.Popen(
        [sys.executable, "-m", "vtpu.monitor", "--hook-path", str(HOOK),
         "--node-name", "bench", "--metrics-port", str(MONITOR_PORT),
         "--feedback-interval", "0.5", "-v"],
        env=env, stdout=logf, stderr=subprocess.STDOUT, text=True)


def read_region_gate_ns(name: str) -> int:
    from vtpu.monitor.region import RegionReader

    reader = RegionReader(str(HOOK / "containers" / f"pod{name}_main"
                              / "usage.cache"))
    snap = reader.read()
    return getattr(snap, "gate_blocked_ns", 0) if snap else 0


def main() -> int:
    subprocess.run(["make", "-C", str(REPO / "libvtpu")],
                   check=True, capture_output=True)
    if HOOK.exists():
        shutil.rmtree(HOOK)
    HOOK.mkdir(parents=True)
    queue = HOOK / "queue.busy"

    env_h = tenant_env("H", 1, queue)
    env_l = tenant_env("L", 0, queue)

    # -- solo
    solo = run_smoke(env_h, H_EXECS)["exec_seconds"] / H_EXECS

    # -- contended: L saturates the shared chip, no monitor
    low = start_low(env_l)
    time.sleep(2)  # L's queue occupancy established
    contended = run_smoke(env_h, H_EXECS)["exec_seconds"] / H_EXECS
    low.kill()
    low.wait()
    time.sleep(1)

    # -- protected: monitor feedback gates the low tenant
    mon = start_monitor()
    low = start_low(env_l)
    time.sleep(2)
    # engage: a short H burst makes H's region "active"; the census blocks
    # L within a feedback interval, so the measured run starts gated
    run_smoke(env_h, 10)
    protected = run_smoke(env_h, H_EXECS)["exec_seconds"] / H_EXECS
    # gate_blocked_ns accrues when a gated execute RELEASES; H is idle now,
    # so the census expires (10 s active window) and the monitor lifts the
    # gate — wait for that, then read L's accumulated blocked time
    deadline = time.time() + 20
    l_gate_ns = 0
    while time.time() < deadline:
        l_gate_ns = read_region_gate_ns("L")
        if l_gate_ns > 0:
            break
        time.sleep(1)
    low.kill()
    low.wait()
    mon.terminate()
    try:
        mon.wait(timeout=15)
    except subprocess.TimeoutExpired:
        mon.kill()

    contention_pct = (contended - solo) / solo * 100
    protected_pct = (protected - solo) / solo * 100
    evidence = {
        "harness": "hack/qos_benefit_c.py",
        "why_not_real_chip": "a real chip belongs to one process at a time "
                             "under the installed libtpu (PR 21 attach "
                             "probe); this is an EMULATED chip, its times "
                             "are the fake plugin's, not a device's",
        "stack": "pjrt_smoke -> libvtpu.so (real shim) -> fake_pjrt.so with "
                 "FAKE_PJRT_SHARED_QUEUE (cross-process serial chip), real "
                 "vtpu.monitor feedback loop",
        "exec_ns": EXEC_NS,
        "h_mean_step_ms": {
            "solo": round(solo * 1e3, 1),
            "contended": round(contended * 1e3, 1),
            "protected": round(protected * 1e3, 1),
        },
        "contention_cost_percent": round(contention_pct, 1),
        "protected_vs_solo_percent": round(protected_pct, 1),
        "low_gate_blocked_s": round(l_gate_ns / 1e9, 2),
        "criteria": {
            "contended_minus_solo_ge_10pct": contention_pct >= 10.0,
            "protected_within_10pct_of_solo": abs(protected_pct) <= 10.0,
            "low_gated": l_gate_ns > 5e9,
        },
    }
    evidence["ok"] = all(evidence["criteria"].values())
    (REPO / "build").mkdir(exist_ok=True)
    (REPO / "build" / "QOS_BENEFIT.json").write_text(
        json.dumps(evidence, indent=2) + "\n")
    print(json.dumps(evidence, indent=2))
    return 0 if evidence["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
