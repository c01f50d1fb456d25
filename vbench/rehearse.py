"""Compile-only rehearsal: the executables a configuration's engine warms,
compiled for a described v5e without a chip, with what each needs of the
device's memory.

    JAX_PLATFORMS=cpu python -m vbench.rehearse <config> [decode|all]

The engine is built on the CPU at the configuration's real sizes (weights
as shapes only), every jitted function it holds is swapped for a proxy that
lowers and compiles it against the TPU topology at the call's shapes, and
the engine's own ``_warm_executables`` is called (the recipe of
hack/tpu_compile_probe.py, kept here so the benchmark owns its copy).
``decode`` stops after the decode steps. Nothing runs: this gives no time
and no result, only what the chip's compiler accepts and how much memory a
step takes beside its arguments, which is what sizes the pool.
"""

from __future__ import annotations

import json
import os
import sys
import time


class _Done(Exception):
    pass


class CompileOnly:
    """Stands in for one jitted function: each call compiles it for the
    described TPU at the call's shapes and returns abstract outputs."""

    def __init__(self, name, fn, to_tpu, log, only=None):
        self.name, self.fn, self.to_tpu, self.log = name, fn, to_tpu, log
        self.only = only

    def __call__(self, *args, **kwargs):
        import jax

        if self.only is not None and self.name not in self.only:
            raise _Done()
        args, kwargs = jax.tree.map(self.to_tpu, (args, kwargs))
        lowered = self.fn.lower(*args, **kwargs)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        self.log.append({
            "fn": self.name,
            "static": {k: v for k, v in kwargs.items()
                       if isinstance(v, (int, bool))},
            "compile_s": round(time.perf_counter() - t0, 1),
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes
                           + mem.temp_size_in_bytes),
        })
        return jax.tree.map(
            lambda info, sh: jax.ShapeDtypeStruct(
                info.shape, info.dtype, sharding=sh),
            lowered.out_info, compiled.output_shardings)


def rehearse(cfg: dict, tpu_device, only=None) -> list[dict]:
    """Compile what ``cfg``'s engine warms (or, with ``only``, the named
    engine attributes up to the first other one) for ``tpu_device``."""
    import importlib

    import jax
    from jax.sharding import SingleDeviceSharding

    from vbench import weights

    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    sut = importlib.import_module(f"vbench.sut.{cfg['family']}")
    specs = ref.weight_specs(cfg)
    on_tpu = SingleDeviceSharding(tpu_device)
    shapes = jax.eval_shape(lambda: weights.build(
        weights.seed_key(0), specs, cfg["num_hidden_layers"]))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on_tpu),
        shapes)

    def to_tpu(x):
        if isinstance(x, jax.ShapeDtypeStruct) or not hasattr(x, "shape") \
                or not hasattr(x, "dtype"):
            return x
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on_tpu)

    real_backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # trace-time routing asks it
    log: list = []
    try:
        eng = sut.build(cfg, params)
        for attr, fn in list(vars(eng).items()):
            if callable(fn) and hasattr(fn, "lower"):
                setattr(eng, attr, CompileOnly(attr, fn, to_tpu, log, only))
        try:
            eng._warm_executables()
        except _Done:
            pass
    finally:
        jax.default_backend = real_backend
    return log


def main(argv: list) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    from jax.experimental import topologies

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "vbench", "configs", f"{argv[0]}.json")) as f:
        cfg = json.load(f)
    only = ({"_decode_sampled"} if len(argv) > 1 and argv[1] == "decode"
            else None)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for row in rehearse(cfg, topo.devices[0], only):
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
