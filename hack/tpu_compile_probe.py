"""Compile-only pre-check: every executable chip_smoke.py's engines warm,
compiled for a v5e WITHOUT a chip.

    JAX_PLATFORMS=cpu python hack/tpu_compile_probe.py [engine-name ...]

libtpu can describe a TPU topology and run the real TPU compiler (Mosaic
included) on a machine that has no TPU. This script builds each engine of
the chip run on the CPU at full width, swaps every jitted function the
engine holds for a proxy that LOWERS AND COMPILES it against the v5e
topology instead of executing it, and then calls the engine's own
``_warm_executables``: so exactly the set of executables a chip run would
compile gets compiled, at the same shapes, and a kernel Mosaic refuses
fails here, in minutes and for free, instead of on budgeted chip time.

Trace-time routing reads ``jax.default_backend()`` (kernel vs gather,
interpret vs compiled), so it is patched to say "tpu" while the probe runs.
What cannot be checked ahead of the chip stays unchecked: the host loop
against a real asynchronous device, placement, the process model.
"""

from __future__ import annotations

import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import dataclasses  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding)

import chip_smoke  # noqa: E402
from vtpu.models import init_params  # noqa: E402
from vtpu.serving import ServingEngine  # noqa: E402

TOPOLOGY = "v5e:2x2"


class CompileOnly:
    """Stands in for one jitted function: each call compiles it for the
    TPU topology at the call's shapes and returns abstract outputs."""

    def __init__(self, name: str, fn, to_tpu, log: list):
        self.name, self.fn, self.to_tpu, self.log = name, fn, to_tpu, log

    def __call__(self, *args, **kwargs):
        args, kwargs = jax.tree.map(self.to_tpu, (args, kwargs))
        lowered = self.fn.lower(*args, **kwargs)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        secs = time.perf_counter() - t0
        text = compiled.as_text()
        mem = compiled.memory_analysis()
        self.log.append({
            "fn": self.name, "compile_s": round(secs, 1),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "temp_mib": round(mem.temp_size_in_bytes / 2**20)})
        print(f"    {self.name}: {secs:.1f}s, "
              f"{self.log[-1]['tpu_custom_calls']} custom calls, "
              f"temp {self.log[-1]['temp_mib']} MiB", flush=True)
        return jax.tree.map(
            lambda info, sh: jax.ShapeDtypeStruct(
                info.shape, info.dtype, sharding=sh),
            lowered.out_info, compiled.output_shardings)


def probe_engine(name: str, params, cfg, serving, tpu_devices,
                 tp: int = 0) -> list:
    """Compile everything *serving*'s engine warms; ``tp`` > 0 builds it
    over a ('tp',) mesh of that many chips."""
    cpu_mesh = tpu_mesh = None
    if tp:
        cpu_mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",))
        tpu_mesh = Mesh(np.array(tpu_devices[:tp]), ("tp",))
    eng = ServingEngine(params, cfg, serving, mesh=cpu_mesh)
    if tp:
        # what the trunk closes over at trace time (shard_map, sharding
        # constraints) must name TPU devices like the operands below
        eng.model.mesh = tpu_mesh

    def to_tpu(x):
        if isinstance(x, jax.ShapeDtypeStruct) or not hasattr(x, "shape") \
                or not hasattr(x, "dtype"):
            return x
        sharding = getattr(x, "sharding", None)
        if isinstance(sharding, NamedSharding):
            sharding = NamedSharding(tpu_mesh, sharding.spec)
        elif tp:  # host values and uncommitted arrays replicate
            sharding = NamedSharding(tpu_mesh, PartitionSpec())
        else:
            sharding = SingleDeviceSharding(tpu_devices[0])
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    log: list = []
    for attr, fn in list(vars(eng).items()):
        if callable(fn) and hasattr(fn, "lower"):
            setattr(eng, attr, CompileOnly(attr, fn, to_tpu, log))
    print(f"  {name}: warming (compile-only)", flush=True)
    eng._warm_executables()
    return log


def main(argv: list) -> int:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    tpu_devices = list(topo.devices)
    print(f"topology {TOPOLOGY}: {len(tpu_devices)} x "
          f"{tpu_devices[0].device_kind}", flush=True)
    cfg, serving = chip_smoke.ttft_server.preset("tpu")
    plans = chip_smoke.flagship_plans(cfg)
    long_cfg = plans["long_cfg"]
    engines = {
        "server": (cfg, serving, 0),
        "paged-bf16": (long_cfg, plans["paged"], 0),
        "paged-int8": (dataclasses.replace(long_cfg, kv_int8=True),
                       plans["paged"], 0),
        "loop": (plans["loop_cfg"], plans["loop"], 0),
        "fused-spec": (plans["loop_cfg"], plans["spec"], 0),
        "tp4-paged": (long_cfg, plans["paged"], 4),
        "replica": (plans["loop_cfg"], plans["replica"], 0),
    }
    unknown = set(argv) - set(engines)
    if unknown:
        print(f"unknown engine(s) {sorted(unknown)}; have {list(engines)}",
              file=sys.stderr)
        return 2
    params = jax.jit(lambda k: init_params(k, cfg))(jax.random.key(0))
    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        total = 0.0
        for name in argv or engines:
            ecfg, eserving, tp = engines[name]
            log = probe_engine(name, params, ecfg, eserving, tpu_devices, tp)
            secs = sum(e["compile_s"] for e in log)
            total += secs
            print(f"  {name}: {len(log)} executables, {secs:.0f}s of "
                  f"compilation", flush=True)
    finally:
        jax.default_backend = real_backend
    print(f"all compiled: {total:.0f}s of TPU compilation in total")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
