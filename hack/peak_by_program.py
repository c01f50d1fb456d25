"""Which program sets a cell's ``memory_peak_bytes``: the cell's engine built
and warmed as ``vbench.run`` does, with the device's ``peak_bytes_in_use``
and ``bytes_in_use`` printed after the weights, after the build, and after
every jitted call of the engine that raised the peak (what a loaded program
keeps resident shows as a rise that stays in use); then two chunked prompts
served.

    python hack/peak_by_program.py <cell> <seed>

From a checkout's root, through the chip tool (``memory_stats()`` is None
on the CPU); one line of JSON an event. PERF.md, section 6, PR 38, found
with it that `dsv2_longgen`'s 14 MB are the chunk programs' own size.
"""

import importlib
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402

from vbench import manifest, run, weights  # noqa: E402


class Watched:
    """A jitted function of the engine; says when a call raised the peak."""

    def __init__(self, name, fn, say):
        self.name, self.fn, self.say = name, fn, say

    def __call__(self, *args, **kwargs):
        dev = jax.devices()[0]
        before = dev.memory_stats()["peak_bytes_in_use"]
        out = jax.block_until_ready(self.fn(*args, **kwargs))
        raised = dev.memory_stats()["peak_bytes_in_use"] - before
        if raised:
            self.say(self.name, raised=raised, static={
                k: v for k, v in kwargs.items() if isinstance(v, (int, bool))})
        return out

    def __getattr__(self, name):
        return getattr(self.fn, name)


def main(argv) -> int:
    run.place_cache()
    root = os.getcwd()
    man = manifest.load(root)
    cfg = manifest.config(man, root, manifest.cell(man, argv[0])["config"])
    dev = jax.devices()[0]

    def say(at, **info):
        stats = dev.memory_stats()
        print(json.dumps({"at": at, **info,
                          "peak": stats["peak_bytes_in_use"],
                          "in_use": stats["bytes_in_use"]}), flush=True)

    ref = importlib.import_module(f"vbench.reference.{cfg['family']}")
    sut = importlib.import_module(f"vbench.sut.{cfg['family']}")
    w = weights.make_all(int(argv[1]), ref.weight_specs(cfg),
                         cfg["num_hidden_layers"],
                         weights.layer_kinds(ref, cfg))
    jax.block_until_ready(w)
    say("weights")
    eng = sut.build(cfg, w)
    del w
    say("built")
    for attr, fn in list(vars(eng).items()):
        if callable(fn) and hasattr(fn, "lower"):
            setattr(eng, attr, Watched(attr, fn, say))
    eng.start()
    say("started")
    rng = np.random.default_rng(0)
    for n in (run.WARM_PROMPT, 3000, 9000):
        req = eng.submit(rng.integers(1, 1000, n).astype(np.int32),
                         max_new_tokens=8)
        list(req.stream())
        say("served", prompt=n)
    eng.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
