"""Every program a toy engine warms, by the sha256 of its lowered text
(ISSUE 30). A change meant to leave the compiled programs as they are
proves it here; one meant to change some shows which in the diff of
tests/program_digests.json, rewritten from the repo's root by

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m tests.test_program_identity

Equal text on the CPU is equal jaxpr and equal lowering rules; it does not
see what only a TPU lowering holds (a Pallas kernel's serialized body,
which carries the call stack's file and line numbers)."""

import hashlib
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from vtpu.models import ModelConfig, init_params
from vtpu.models.moe import MoEConfig, init_moe_params
from vtpu.serving import ServingConfig, ServingEngine
from vtpu.serving.adapters import MoeSlotModel, TransformerSlotModel

DIGESTS = pathlib.Path(__file__).with_name("program_digests.json")

FAMILIES = {
    "dense": (
        lambda int8: ModelConfig(
            vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_seq=32,
            head_dim=16, dtype=jnp.bfloat16, use_pallas=False, kv_int8=int8),
        init_params, TransformerSlotModel),
    "expert": (
        lambda int8: MoEConfig(
            vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=32, n_experts=4,
            top_k=2, max_seq=32, head_dim=16, dtype=jnp.bfloat16,
            kv_int8=int8),
        init_moe_params, MoeSlotModel),
}
# name: (int8 cache, kv_page, devices on the 'tp' axis)
LAYOUTS = {
    "paged_bf16": (False, 8, 0),
    "paged_int8": (True, 8, 0),
    "dense_bf16": (False, None, 0),
    "tp2_paged_bf16": (False, 8, 2),
}
# between them the two warm every step function of vtpu/models/slots.py:
# batched admission, chunked prefill and the decode step; the serial
# admission and the speculative step
SHAPES = {
    "async": dict(prefill_batch_sizes=(1, 2)),
    "spec": dict(spec_tokens=2),
}
CASES = [(family, layout) for family in FAMILIES for layout in LAYOUTS]


class _Recorder:
    """Stands where a jitted attribute of the engine stood: a call lowers
    it at the call's arguments, notes the text's digest, then runs it."""

    def __init__(self, name, fn, log):
        self.name, self.fn, self.log = name, fn, log

    def __call__(self, *args, **kwargs):
        text = self.fn.lower(*args, **kwargs).as_text()
        static = [str(a) for a in args if type(a) in (int, bool)] + [
            f"{k}={v}" for k, v in sorted(kwargs.items())
            if isinstance(v, (int, bool))]
        shapes = ["x".join(map(str, a.shape)) for a in args[2:]
                  if hasattr(a, "shape")]
        key = f"{self.name}[{','.join(static)}|{','.join(shapes)}]"
        self.log.setdefault(key, []).append(
            hashlib.sha256(text.encode()).hexdigest()[:16])
        return self.fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self.fn, item)


def warmed_programs(family, layout):
    """{shape/program[static arguments|argument shapes]: digests, one a
    lowering, in one string} over everything the engine's warm-up
    dispatches."""
    config, init, adapter = FAMILIES[family]
    int8, page, tp = LAYOUTS[layout]
    cfg = config(int8)
    mesh = Mesh(np.array(jax.devices()[:tp]), ("tp",)) if tp else None
    out = {}
    for shape, extra in SHAPES.items():
        serving = ServingConfig(
            slots=2, prefill_buckets=(16,), max_new_tokens=4, kv_page=page,
            prefill_chunk=8, **extra)
        eng = ServingEngine(serving=serving, model=adapter(
            init(jax.random.key(0), cfg), cfg, mesh=mesh, kv_page=page))
        log = {}
        for attr, fn in list(vars(eng).items()):
            if callable(fn) and hasattr(fn, "lower"):
                setattr(eng, attr, _Recorder(attr, fn, log))
        with eng._on_device():
            eng._warm_executables()
        out.update({f"{shape}/{key}": " ".join(val)
                    for key, val in log.items()})
    return out


@pytest.mark.parametrize("family,layout", CASES)
def test_warmed_programs_lower_to_the_recorded_text(family, layout):
    want = json.loads(DIGESTS.read_text())[f"{family}/{layout}"]
    got = warmed_programs(family, layout)
    assert sorted(got) == sorted(want)
    assert not {key: (got[key], want[key])
                for key in got if got[key] != want[key]}


if __name__ == "__main__":
    DIGESTS.write_text(json.dumps(
        {f"{family}/{layout}": warmed_programs(family, layout)
         for family, layout in CASES}, indent=1, sort_keys=True) + "\n")
