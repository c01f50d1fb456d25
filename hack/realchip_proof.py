"""Prove libvtpu against the installed libtpu on a real chip.

The reference's de-facto isolation benchmark execs ``nvidia-smi`` + a CUDA
sample inside a capped container and asserts the cap is live
(reference test/e2e/pod/test_pod.go:85-120). This is the vTPU equivalent.
libvtpu is delivered the way the chart's initContainer installs it
(charts/vtpu .../daemonset.yaml ld.so.preload flow; reference
lib/nvidia/ld.so.preload:1, docker/vgpu-init.sh:70-75): ``LD_PRELOAD``ed
into a plain JAX process. JAX dlopens the installed ``libtpu.so`` itself,
and libvtpu's interposed dlsym() hands back the wrapping trampoline when
the loader resolves ``GetPjrtApi``.

Asserted, all against the real runtime:
  (a) a jitted JAX workload runs end-to-end through the wrapper and is
      numerically correct (struct_size skew, extension chain, event
      semantics of the real plugin — not fake_pjrt.cc);
  (b) an over-cap allocation is rejected with the tagged
      RESOURCE_EXHAUSTED error and the tenant SURVIVES (next allocation
      works) — the cap is enforcement, not a crash;
  (c) the mmap'ed shared region shows live usage from outside the
      workload process (the monitor's view);
  (d) the shim's own counters confirm executes were intercepted (the
      preload could silently fall back to the unwrapped plugin otherwise).
Reported, not asserted: the calibration verdict the shim gives this
runtime's completion events (``shim_stats.calib_verdict``; ROADMAP
Design #10 waits on it).

The parent never touches JAX (a chip belongs to one process at a time); it
builds libvtpu from ``libvtpu/src`` and runs one child.

Usage:  python hack/realchip_proof.py            # parent: build, spawn, verify
        python hack/realchip_proof.py --child    # (internal)
Prints the result as JSON; exit 0 only if every assertion held.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
LIBVTPU = REPO / "libvtpu" / "build" / "libvtpu.so"
CAP_BYTES = 512 * 1024 * 1024  # TPU_DEVICE_MEMORY_LIMIT_0=512m
OVERCAP_ELEMS = 600 * 1024 * 1024 // 4  # 600 MiB of f32 > cap


def child() -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    out: dict = {"cap_bytes": CAP_BYTES}
    devs = jax.devices()
    out["devices"] = [str(d) for d in devs]
    out["platform"] = devs[0].platform
    out["device_kind"] = devs[0].device_kind

    # (a) real workload through the wrapper, numerically checked. HIGHEST
    # precision forces true-f32 MXU passes so the check is tight (default
    # TPU f32 matmul uses bf16 passes, ~1e-2 relative error).
    rng = np.random.RandomState(0)
    a = np.asarray(rng.standard_normal((2048, 2048)), np.float32)
    b = np.asarray(rng.standard_normal((2048, 2048)), np.float32)
    got = np.asarray(jax.jit(lambda x, y: jnp.dot(x, y, precision="highest"))(a, b))
    want = a @ b
    scale = float(np.max(np.abs(want)))
    out["matmul_max_abs_err"] = float(np.max(np.abs(got - want)))
    out["matmul_ok"] = bool(out["matmul_max_abs_err"] < 1e-3 * scale)

    # (c, live view) region written by libvtpu inside this process. Hold a
    # live buffer while reading: freed temporaries correctly drop to zero.
    held = jax.device_put(np.ones((8 * 1024 * 1024,), np.float32))  # 32 MiB
    held.block_until_ready()
    sys.path.insert(0, str(REPO))
    from vtpu.monitor.region import RegionReader

    snap = RegionReader(os.environ["VTPU_SHARED_REGION"]).read()
    out["region_valid"] = snap.valid
    out["region_used_bytes"] = snap.devices[0].hbm_used_bytes
    out["region_limit_bytes"] = snap.devices[0].hbm_limit_bytes

    # (b) over-cap allocation: tagged RESOURCE_EXHAUSTED, tenant survives.
    out["overcap_rejected"] = False
    try:
        big = jax.device_put(np.zeros((OVERCAP_ELEMS,), np.float32))
        big.block_until_ready()
        out["overcap_msg"] = "allocation unexpectedly succeeded"
    except Exception as e:  # jaxlib's XlaRuntimeError
        msg = str(e)
        out["overcap_rejected"] = ("RESOURCE_EXHAUSTED" in msg
                                   and "vtpu: HBM limit exceeded" in msg)
        out["overcap_msg"] = msg.splitlines()[0][:300]

    small = jax.device_put(np.ones((1024, 1024), np.float32))
    out["post_overcap_ok"] = bool(float(jnp.sum(small)) == 1024 * 1024)

    # (d) the shim really intercepted this traffic (CDLL on the preloaded
    # path returns the live copy).
    try:
        import ctypes

        lib = ctypes.CDLL(str(LIBVTPU))
        lib.vtpu_stats_json.restype = ctypes.c_size_t
        buf = ctypes.create_string_buffer(2048)
        if lib.vtpu_stats_json(buf, ctypes.c_size_t(len(buf))):
            stats = json.loads(buf.value.decode())
            out["shim_stats"] = stats
            out["intercepted"] = stats.get("executes", 0) > 0
    except Exception as exc:
        out["intercepted"] = False
        out["shim_stats_error"] = str(exc)

    print("CHILD_RESULT " + json.dumps(out), flush=True)


def run() -> dict:
    region_path = str(REPO / "build" / "realchip_proof.cache")
    os.makedirs(os.path.dirname(region_path), exist_ok=True)
    if os.path.exists(region_path):
        os.unlink(region_path)

    env = dict(os.environ)
    env["TPU_DEVICE_MEMORY_LIMIT_0"] = str(CAP_BYTES)
    env["VTPU_SHARED_REGION"] = region_path
    env["LD_PRELOAD"] = str(LIBVTPU)
    env.setdefault("LIBVTPU_LOG_LEVEL", "2")

    r = subprocess.run([sys.executable, __file__, "--child"], env=env,
                       capture_output=True, text=True, timeout=560)
    result: dict = {}
    got = None
    for line in r.stdout.splitlines():
        if line.startswith("CHILD_RESULT "):
            got = json.loads(line[len("CHILD_RESULT "):])
    if got is None:
        result["ok"] = False
        result["error"] = ("child produced no result; rc=%d\nstdout: %s\nstderr: %s"
                           % (r.returncode, r.stdout[-1500:], r.stderr[-3000:]))
        return result
    result.update(got)
    # what the shim said on the way (why calibration ended as it did)
    result["shim_log"] = [line for line in r.stderr.splitlines()
                          if line.startswith("[libvtpu]")][-20:]

    # (c, monitor view) after the child exits, parse the region file the way
    # the node monitor does — cross-process, no libvtpu in this process.
    sys.path.insert(0, str(REPO))
    from vtpu.monitor.region import RegionReader

    snap = RegionReader(region_path).read()
    result["monitor_region_valid"] = snap.valid
    result["monitor_peak_bytes"] = snap.devices[0].hbm_peak_bytes

    ok = (result.get("platform") == "tpu"
          and result.get("matmul_ok") and result.get("overcap_rejected")
          and result.get("post_overcap_ok") and result.get("region_valid")
          and result.get("region_used_bytes", 0) > 0
          and result.get("intercepted")
          and result.get("monitor_region_valid")
          and result.get("monitor_peak_bytes", 0) > 0)
    result["ok"] = bool(ok)
    return result


def parent() -> int:
    # from libvtpu/src every time: never a .so already lying in build/
    build = subprocess.run(["make", "-B", "-C", str(REPO / "libvtpu")],
                           capture_output=True, text=True)
    if build.returncode != 0:
        print(build.stdout + build.stderr, file=sys.stderr)
        return 1
    result = run()
    print(json.dumps(result, indent=2))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(parent())
