"""A planted fault for MiniCPM-SALA's cell: the selection replaced by the
most recent ``topk`` blocks (4096 tokens), in the decode step and in the
chunks alike, as a port that served a sliding window in the sparse layers'
place would. Runs the benchmark's own entry point with the blocks' scores
replaced by their index; the result line has to read ``"correct": false``
at the committed limits (PERF.md section 2).

    python hack/sala_recent_selection.py --workload sala_longsessions \\
        --seed <n> --seconds 51 --trace 0
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def recent(windows, positions, sizes, n_blocks):
    """``blocksparse.block_scores``'s shape, a later block the higher."""
    import jax.numpy as jnp

    b = jnp.arange(n_blocks)
    mine = (positions // sizes.block_size)[..., None, None]
    score = jnp.broadcast_to(
        b.astype(jnp.float32), windows.shape[:-1] + (n_blocks,))
    return jnp.where(b <= mine, score, -jnp.inf)


def main() -> int:
    from vbench import run
    from vtpu.ops import blocksparse

    blocksparse.block_scores = recent
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
