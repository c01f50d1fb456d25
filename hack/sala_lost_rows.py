"""A planted fault for MiniCPM-SALA's cell: every prefill chunk starts its
linear-attention layers from zeros instead of the slot's carried rows, as a
chunked prefill that lost the recurrent matrices at each boundary would
(hack/hybrid_lost_carry.py's fault). Runs the benchmark's own entry point
with the chunk's entry point wrapped: the slot's rows are zeroed in the
state a chunk is handed. The result line has to read ``"correct": false``
at the committed limits (PERF.md section 2 has the reading).

    python hack/sala_lost_rows.py --workload sala_longsessions \\
        --seed <n> --seconds 51 --trace 0
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def lossy(prefill_chunk):
    """``sparselinear_prefill_chunk`` over a state whose rows of the
    chunk's slot are gone."""
    import jax
    import jax.numpy as jnp

    def chunk(params, cfg, state, tokens, slot, *rest):
        rows = state["s"]
        gone = jax.lax.dynamic_update_slice(
            rows, jnp.zeros_like(rows[:, :1]), (0, slot, 0, 0, 0))
        return prefill_chunk(params, cfg, {**state, "s": gone}, tokens, slot,
                             *rest)

    return chunk


def main() -> int:
    from vbench import run
    from vtpu.models import sparselinear

    sparselinear.sparselinear_prefill_chunk = lossy(
        sparselinear.sparselinear_prefill_chunk)
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
