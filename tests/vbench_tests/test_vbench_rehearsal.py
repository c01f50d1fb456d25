"""Compile-only rehearsal of both configurations' decode step at their
real widths, for a described v5e, with no chip (on-chip-measurement guide,
section 2): what the chip's compiler would refuse, or what would no longer
fit the chip's memory beside the pool, fails here at no chip time.

All of it in this one file, the topology described inside a fixture: only
the worker that is given this file loads the TPU's library.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from vbench_toyroot import REPO  # noqa: E402

pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")

HBM = 16 * 2**30


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return topo.devices[0]


@pytest.fixture(scope="module")
def decode_steps(v5e):
    from vbench.rehearse import rehearse

    out = {}
    for name in ("olmoe-1b-7b-8l", "deepseek-llm-7b-15l"):
        with open(os.path.join(REPO, "vbench", "configs",
                               f"{name}.json")) as f:
            cfg = json.load(f)
        out[name] = (cfg, rehearse(cfg, v5e, only={"_decode_sampled"}))
    return out


@pytest.mark.parametrize("name", ["olmoe-1b-7b-8l", "deepseek-llm-7b-15l"])
def test_decode_step_compiles_and_fits_beside_the_pool(decode_steps, name):
    cfg, log = decode_steps[name]
    windows = len(set(cfg["serving"]["prefill_buckets"])
                  | {cfg["max_position_embeddings"]})
    assert len(log) == 2 * windows       # host-fed and device-fed, a window
    for row in log:
        assert row["fn"] == "_decode_sampled"
        assert row["peak_bytes"] < 0.92 * HBM, row
    # weights, pool and the largest step fill most of the chip
    assert max(r["peak_bytes"] for r in log) > 0.60 * HBM


@pytest.mark.parametrize("name", ["olmoe-1b-7b-8l", "deepseek-llm-7b-15l"])
def test_widest_window_takes_the_kernel_route(decode_steps, name):
    cfg, log = decode_steps[name]
    assert log[-1]["tpu_custom_calls"] == cfg["num_hidden_layers"]
