"""A planted fault for DeepSeek-V2's cell: the softmax scale without YaRN's
magnitude correction (``(dn + dr)^-0.5`` alone, where the published scale
is that times ``(0.1 * mscale_all_dim * ln(factor) + 1)^2`` = 1.5896), in
the decode step's walk and in the chunks alike, as a port that read
``mscale_all_dim`` as 0 would serve it. Runs the benchmark's own entry
point with that one property replaced; the result line has to read
``"correct": false`` at the committed limits (PERF.md section 2).

    python hack/mla_plain_scale.py --workload dsv2_longgen \\
        --seed <n> --seconds 51 --trace 0
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from vbench import run
    from vtpu.models.latent import LatentConfig

    LatentConfig.attn_scale = property(
        lambda self: (self.nope_dim + self.rope_dim) ** -0.5)
    return run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
