"""What the chip's memory gives a pass that reads an array and writes it
back, by how its copies are ordered: no arithmetic, copies between HBM and
VMEM alone.

    python hack/hbm_copy_probe.py [--tiny] [--tiles 2,8,16]

Eight layers' worth of `granite4h_sessions`' recurrent state ([rows, 128]
float32, 1.07 GB) in tiles of ``--tiles`` MB: ``read`` (HBM to VMEM only),
``write`` (VMEM to HBM only), ``concurrent`` (a tile's read and the tile
before's write in flight together: what a BlockSpec pipeline does) and
``phased`` (one copy in flight at a time, reads and writes alternating).
The state kernel of ``vtpu/ops/ssm_step.py`` is bounded by ``concurrent``
(PERF.md section 6, PR 36). ``--tiny`` compiles nothing for a TPU: it runs
a small array interpreted on the CPU and proves only that the script runs.
"""

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MODES = ("read", "write", "concurrent", "phased")
ROWS_PER_MB = 2048  # rows of 128 float32
REPEATS = 8


def kernel(h_ref, o_ref, buf, rsem, wsem, *, mode, tile, nt):
    def rd(i):
        return pltpu.make_async_copy(
            h_ref.at[pl.ds(i * tile, tile)], buf.at[i % 3], rsem.at[i % 3])

    def wr(i):
        return pltpu.make_async_copy(
            buf.at[i % 3], o_ref.at[pl.ds(i * tile, tile)], wsem.at[i % 3])

    def body(i, carry):
        if mode == "read":
            @pl.when(i + 1 < nt)
            def _():
                rd(i + 1).start()
            rd(i).wait()
        elif mode == "write":
            wr(i).start()

            @pl.when(i >= 1)
            def _():
                wr(i - 1).wait()
        elif mode == "concurrent":
            @pl.when(i >= 2)
            def _():
                wr(i - 2).wait()

            @pl.when(i + 1 < nt)
            def _():
                rd(i + 1).start()
            rd(i).wait()
            wr(i).start()
        else:  # phased
            @pl.when(i + 1 < nt)
            def _():
                rd(i + 1).start()
                rd(i + 1).wait()
            wr(i).start()
            wr(i).wait()
        return carry

    if mode != "write":
        rd(0).start()
    if mode == "phased":
        rd(0).wait()
    jax.lax.fori_loop(0, nt, body, 0)
    if mode == "write":
        wr(nt - 1).wait()
    if mode == "concurrent":
        wr(nt - 2).wait()
        wr(nt - 1).wait()


def copy(h, mode, tile, interpret=False):
    return pl.pallas_call(
        functools.partial(kernel, mode=mode, tile=tile, nt=h.shape[0] // tile),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(h.shape, h.dtype),
        scratch_shapes=[pltpu.VMEM((3, tile, 128), jnp.float32),
                        pltpu.SemaphoreType.DMA((3,)),
                        pltpu.SemaphoreType.DMA((3,))],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=3 * tile * 512 + (16 << 20)),
        interpret=interpret, name="hbm_copy_probe")(h)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--tiles", default="2,8,16")
    args = ap.parse_args(argv)
    if args.tiny:
        jax.config.update("jax_platforms", "cpu")
    rows = 64 if args.tiny else 8 * 64 * 64 * 64
    tiles = [8] if args.tiny else [
        int(mb) * ROWS_PER_MB for mb in args.tiles.split(",")]
    h = jax.random.normal(jax.random.key(0), (rows, 128), jnp.float32)
    for mode in MODES:
        for tile in tiles:
            fn = jax.jit(functools.partial(copy, mode=mode, tile=tile,
                                           interpret=args.tiny),
                         donate_argnums=(0,))
            h = jax.block_until_ready(fn(h))
            t0 = time.perf_counter()
            for _ in range(REPEATS):
                h = fn(h)
            jax.block_until_ready(h)
            ms = 1e3 * (time.perf_counter() - t0) / REPEATS
            moved = h.size * 4 * (1 if mode in ("read", "write") else 2)
            print(json.dumps({
                "device": jax.devices()[0].device_kind, "mode": mode,
                "tile_mb": tile / ROWS_PER_MB, "ms": round(ms, 3),
                "gb_per_s": round(moved / ms / 1e6, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
