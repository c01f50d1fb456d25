"""A planted fault for the hybrid family's cell: every prefill chunk starts
from zeros instead of the slot's carried rows, as a chunked prefill that
lost the recurrent state at each boundary would. Runs the benchmark's own
entry point with that one function replaced; the result line has to read
``"correct": false`` at the committed limits (PERF.md section 2).

    python hack/hybrid_lost_carry.py --workload granite4h_sessions \\
        --seed <n> --seconds 51 --trace 0
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax.numpy as jnp

    from vbench import run
    from vtpu.models import hybrid

    def lost(state, slot, offset):
        conv, h = state["conv"][:, slot], state["h"][:, slot]
        return jnp.zeros_like(conv)[:, None], jnp.zeros_like(h)[:, None]

    hybrid.carried_rows = lost
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
