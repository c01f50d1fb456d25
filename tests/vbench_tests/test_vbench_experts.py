"""The two readers of PR 41 (vbench/metrics/experts_ms_per_chunk.py,
experts_grouped_pct.py): the first on the slice recorded on the chip that
vbench/data keeps, as recorded (its one prefill launch is an admission's,
read here under the chunk program's name too) and on a hand-made chunk
whose answer is plain; the second on counters."""

import copy
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import manifest, scopes  # noqa: E402
from vbench.metrics import experts_grouped_pct, experts_ms_per_chunk  # noqa: E402

MS = 10 ** 9  # a millisecond in the trace's picoseconds
CHUNK = "jit_prefill_chunk_into_slot"
CELLS = ["dsv32_longctx", "dsv2_longgen", "mimo_mixedqueue"]


def _recorded():
    with open(os.path.join(REPO, "vbench", "data",
                           "recorded_scopes.json")) as f:
        return json.load(f)


def _chunks(scoped: bool):
    """Three chunk launches of 40 ms back to back (the middle one is the
    whole one), each 2 ms of routing, 14 ms of experts (a kernel's 10 ms
    inside), 20 ms of attention; ``scoped`` False names the experts' time
    ``mlp`` instead, as a dense model's chunk has it."""
    ops, modules = [], []
    experts = "experts" if scoped else "mlp"
    for i in range(3):
        t = 10 + 40 * i
        modules.append([CHUNK + "(9)", t * MS, 40 * MS])
        for name, at, dur, path in (
                ("%fusion.1 = f32[8]", 0, 2, "jit(f)/route/dot_general:"),
                ("%experts_gate_up.2 = bf16[8]", 2, 10,
                 f"jit(f)/{experts}/experts_gate_up/pallas_call:"),
                ("%fusion.3 = bf16[8]", 12, 4, f"jit(f)/{experts}/add:"),
                ("%fusion.4 = bf16[8]", 16, 20, "jit(f)/attn/dot_general:")):
            ops.append([name, (t + at) * MS, dur * MS, path])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


def test_experts_ms_per_chunk_on_a_plain_chunk():
    """Route and experts, 16 ms a launch; None for a chunk program with no
    ``experts`` scope, for a trace with no chunk launch, and for none."""
    assert experts_ms_per_chunk.of(
        scopes.reduce(_chunks(True))) == pytest.approx(16.0)
    assert experts_ms_per_chunk.of(scopes.reduce(_chunks(False))) is None
    assert experts_ms_per_chunk.of(None) is None


@pytest.mark.parametrize("as_chunk", [False, True],
                         ids=["as_recorded", "admission_as_chunk"])
def test_experts_ms_per_chunk_on_the_recorded_slice(as_chunk):
    """The slice of PR 25's chip run holds decode steps and one admission
    of a sparse model, no chunk: None. The same launch read under the chunk
    program's name gives its ``route`` + ``experts`` time (13.72 ms of its
    15.40: the file is not edited, the copy in memory is renamed)."""
    raw = _recorded()
    if not as_chunk:
        assert experts_ms_per_chunk.of(scopes.reduce(raw)) is None
        return
    raw = copy.deepcopy(raw)
    for dev in raw["devices"].values():
        for module in dev["modules"]:
            module[0] = module[0].replace("jit_admit_step", CHUNK)
    red = scopes.reduce(raw)
    row = red["programs"][CHUNK]
    assert row["launches"] == 1 and "experts" in row["scopes"]
    want = 1e3 * (row["scopes"]["route"] + row["scopes"]["experts"])
    assert experts_ms_per_chunk.of(red) == pytest.approx(want)
    assert 13.0 < want < 14.0


def test_experts_grouped_pct_reads_the_counters():
    """The window's growth of ``expert_rows_grouped`` over that of
    ``expert_rows``; None without the counters (the parent of PR 41) and
    with no launch in the window, never zero for either."""
    stats0 = {"expert_rows": 100, "expert_rows_grouped": 100}
    stats1 = {"expert_rows": 1124, "expert_rows_grouped": 612}
    run = types.SimpleNamespace(
        stats1=stats1, counter=lambda name: stats1[name] - stats0[name])
    assert experts_grouped_pct.read(run) == pytest.approx(50.0)
    run.counter = lambda name: 0
    assert experts_grouped_pct.read(run) is None
    run.stats1 = {"prefill_chunks": 3}
    assert experts_grouped_pct.read(run) is None


@pytest.mark.parametrize("name,source", [
    ("experts_ms_per_chunk", "device_trace"),
    ("experts_grouped_pct", "program_counter")])
def test_the_manifest_names_the_readers(name, source):
    """Both are per-layer metrics of the kernels layer in the three cells
    that hold experts, move ``itl_mean_ms``, and have a reader file."""
    man = manifest.load(REPO)
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    assert entry["workloads"] == CELLS and entry["source"] == source
    assert entry["layer"] == "kernels" and entry["moves"] == "itl_mean_ms"
    for cell in CELLS:
        assert name in [m["name"] for m in
                        manifest.metrics_of(man, "per_layer", cell)]
    assert callable(manifest.reader(REPO, name))
