"""The two readers of PR 45 (vbench/metrics/chunk_attn_ms_per_chunk.py over
vbench/chunk_scopes.py, chunk_attn_kernel_pct.py): the first on a hand-made
trace of chunk launches whose answer is plain, with the kernel under the
scope and with XLA's code under it, and on a program without the scope; the
second on counters; and their entries in the benchmark."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

from vbench import chunk_scopes, manifest, scopes  # noqa: E402

MS = 10 ** 9  # a millisecond in the trace's picoseconds
CHUNK = "jit_prefill_chunk_into_slot"
CELLS = ["dsllm7b_longprompt", "granite4h_sessions", "mimo_mixedqueue",
         "sdar_blockgen"]
NAMES = ["chunk_attn_ms_per_chunk", "chunk_attn_kernel_pct"]


def _chunks(form: str):
    """Three chunk launches of 40 ms back to back (the middle one is the
    whole one) and a decode launch. A chunk: 4 ms of the window's gather,
    then its attention, 12 ms of the chunk kernel with 1 ms of what its
    call prepares (``kernel``), or three of XLA's fusions, 20 ms
    (``xla``), under ``gather_attn/chunk_attn``; ``parent``: those fusions
    under ``gather_attn`` alone, as before PR 45. The decode launch holds a
    gather-route step's attention, which is no chunk's."""
    ops, modules = [], []
    under = {"kernel": "gather_attn/chunk_attn", "xla": "gather_attn/chunk_attn",
             "parent": "gather_attn"}[form]
    for i in range(3):
        t = 10 + 40 * i
        modules.append([CHUNK + "(9)", t * MS, 40 * MS])
        ops.append(["%fusion.1 = bf16[8]", t * MS, 4 * MS,
                    "jit(f)/gather_attn/gather:"])
        if form == "kernel":
            inner = [("%fusion.2 = bf16[8]", 4, 1, "transpose:"),
                     ("%chunk_attn.3 = bf16[8]", 5, 12,
                      "chunk_attn/pallas_call:")]
        else:
            inner = [("%fusion.2 = f32[8]", 4, 8, "dot_general:"),
                     ("%fusion.3 = f32[8]", 12, 7, "reduce_max:"),
                     ("%fusion.4 = bf16[8]", 19, 5, "dot_general:")]
        for name, at, dur, path in inner:
            ops.append([name, (t + at) * MS, dur * MS,
                        f"jit(f)/{under}/{path}"])
        ops.append(["%fusion.9 = bf16[8]", (t + 30) * MS, 8 * MS,
                    "jit(f)/mlp/dot_general:"])
    modules.append(["jit_step(3)", 200 * MS, 10 * MS])
    ops.append(["%fusion.5 = bf16[8]", 201 * MS, 3 * MS,
                "jit(step)/gather_attn/chunk_attn/dot_general:"])
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "spans": []}


@pytest.mark.parametrize("form,want", [("kernel", 13.0), ("xla", 20.0),
                                       ("parent", None)])
def test_chunk_attn_ms_per_chunk_on_a_plain_trace(monkeypatch, form, want):
    """The scope's own time a chunk launch, whichever code runs under it;
    None for a program without the scope. The harness's own copy of the
    vocabulary reads the same operations as ``gather_attn``: nothing of
    them is unscoped."""
    raw = _chunks(form)
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(chunk_scopes, "load",
                        lambda root=None: chunk_scopes.by_scope(raw) or None)
    by = scopes.reduce(raw)["programs"][CHUNK]["scopes"]
    assert "unscoped" not in by
    assert by["gather_attn"] == pytest.approx(
        3 * (4 + (13 if form == "kernel" else 20)) / 1e3)
    read = manifest.reader(REPO, "chunk_attn_ms_per_chunk")
    run = types.SimpleNamespace(trace={"busy_s": 1.0})
    got = read(run)
    assert got == (None if want is None else pytest.approx(want))
    run.trace = None  # a run without a trace reads nothing
    assert read(run) is None


def test_chunk_attn_ms_per_chunk_needs_a_chunk_launch(monkeypatch):
    """A trace with decode launches alone: None, not nought."""
    raw = _chunks("kernel")
    dev = raw["devices"]["/device:TPU:0"]
    dev["modules"] = [m for m in dev["modules"] if m[0].startswith("jit_step")]
    dev["ops"] = [op for op in dev["ops"] if op[3].startswith("jit(step)")]
    assert chunk_scopes.by_scope(raw) == {}
    monkeypatch.setattr(scopes, "load", lambda root=None: scopes.reduce(raw))
    monkeypatch.setattr(chunk_scopes, "load", lambda root=None: None)
    assert chunk_scopes.ms_per_chunk() is None


def test_chunk_attn_kernel_pct_on_counters():
    read = manifest.reader(REPO, "chunk_attn_kernel_pct")
    grown = {"chunk_attn_kernel": 30, "chunk_attn_launches": 40}
    run = types.SimpleNamespace(stats1=grown, counter=grown.__getitem__)
    assert read(run) == 75.0
    grown["chunk_attn_kernel"] = 40
    assert read(run) == 100.0
    run.counter = lambda name: 0  # no chunk in the window
    assert read(run) is None
    run.stats1 = {"prefill_chunks": 3}  # a program without the counters
    assert read(run) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_entries_name_the_four_cells(name):
    man = manifest.load(REPO)
    entry = next(m for m in man["per_layer"] if m["name"] == name)
    assert entry["workloads"] == CELLS
    assert entry["layer"] == "paged pool and attention route"
    assert entry["moves"] == "itl_mean_ms"
    assert entry["source"] == ("device_trace" if "ms" in name
                               else "program_counter")
    for cell in CELLS:
        assert name in [m["name"] for m in manifest.metrics_of(
            man, "per_layer", cell)]
    assert name not in [m["name"] for m in manifest.metrics_of(
        man, "per_layer", "olmoe_chat")]
