"""The held experts' SwiGLU over the rows the router sent each (two Pallas
kernels).

``vtpu.models.moe.held_experts_ffn`` hands this module a launch's rows
``x [T, D]``, the router's columns for the experts held here ``gates [T,
H]`` (zero where a row was not sent to an expert) and the experts' stacks.
A *pair* is a row and a held expert with a gate other than zero; a row has
at most ``min(top_k, H)`` of them. The pairs are laid out expert by expert
in *tiles* of ``tm`` row slots, every expert's first pair at the start of a
tile, so a tile belongs to one expert and a launch has at most ``tiles``
of them whatever the routing (``plan``: sized for every row on as few
experts as the router allows, never a capacity: nothing is dropped).

**Work follows the live tiles.** Both kernels run over a grid whose tile
axis ends at the number of tiles that hold a pair (data, a scalar the grid
reads), and a tile's index maps name its expert's blocks of the stacks: an
expert that drew no row has no tile, and none of its weights are read; a
512-row chunk that routes 16 rows to each of 16 experts multiplies 16
tiles, not the 4096 slots of the worst case nor the 16 x 512 of the
all-rows code.

**No sorted copy of the rows is made, and no pair's output leaves the
chip.** ``gate_up`` picks a tile's rows out of ``x`` (whole in VMEM) with a
one-hot product on the MXU (exact: one term a row), multiplies them into
the expert's ``w_gate`` and ``w_up`` a block of ``D`` at a time, float32
accumulators for both, and writes ``silu(gate) * up * the pair's gate`` in
the rows' dtype (the products rounded to that dtype first, as the all-rows
einsums round theirs). ``down`` multiplies a tile into its expert's
``w_down`` a block of ``F`` at a time and adds each live slot's float32 row
into the output row it came from, which stays in VMEM from the first tile
to the last: a row's pairs are summed in float32 and rounded once, by the
caller.

``held_experts_ffn``'s all-rows code is the same arithmetic as XLA code,
the CPU route and these kernels' reference in the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# row slots a tile. A tile's products run at what its expert's weights take
# to read as long as it has fewer rows than the chip has FLOPs a byte (240
# on a v5e), and the MXU takes a block of weights no faster for fewer rows
# than its 128: a taller tile would only re-read an expert's weights less
# often when it draws more than 128 rows, which a holder of 1/16 of the
# experts sees only from 2048 rows a launch on.
_ROWS = 128
# most of D a step of ``gate_up`` takes of both stacks, most of F a step of
# ``down`` (blocks of 4 MB and 3.7-7.3 MB at the published widths: a step's
# overhead is paid 100-200 times a layer)
_BLOCK = 1024
_VMEM_BYTES = 96 << 20
# what the compiler may want of that beside the buffers ``takes`` counts
# (a tile's ``pick`` of a launch's rows and the like: 3 MB at 2048 rows)
_VMEM_SPARE = 8 << 20
# the most rows a launch was compiled with for a v5e (tests/test_tpu_compile
# .py) and timed with on one (PERF.md section 6, PR 41): more run the
# all-rows code until someone times them
_MOST_ROWS = 2048


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _block(n: int) -> int:
    """The largest multiple of 128 that divides ``n`` and is at most
    ``_BLOCK``, else ``n`` whole."""
    for b in range(_BLOCK, 0, -128):
        if n % b == 0:
            return b
    return n


def _columns(d: int) -> int:
    """Columns of the output a pass of ``down`` holds: half of ``d``."""
    return d // 2 if d % 256 == 0 else d


def takes(t: int, d: int, f: int) -> bool:
    """Whether the kernels take a launch of ``t`` rows ``d`` wide through
    experts ``f`` wide: widths whole in the chip's 128 lanes, no more rows
    than ``_MOST_ROWS``, and what either kernel holds in VMEM within the
    limit both are compiled with: the pipeline's two buffers of each
    operand's block and of the output's, and the accumulators. ``gate_up``
    holds the launch's rows whole, a block of ``D`` of both stacks ``f``
    wide and a tile's two float32 accumulators; ``down`` the launch's
    float32 output ``_columns`` wide, a block of ``F`` of the stack and a
    tile's accumulator."""
    if d % 128 or f % 128 or t > _MOST_ROWS:
        return False
    rows = _round_up(t, 16)
    tm = min(_ROWS, rows)
    td, tf, dn = _block(d), _block(f), _columns(d)
    gate_up = (2 * rows * d * 2 + 2 * 2 * td * f * 2
               + 2 * tm * f * 2 + 2 * tm * f * 4)
    down = (2 * rows * dn * 4 + 2 * tf * dn * 2
            + 2 * tm * tf * 2 + tm * dn * 4)
    return max(gate_up, down) <= _VMEM_BYTES - _VMEM_SPARE


def plan(t: int, h: int, top_k: int) -> tuple[int, int, int]:
    """(rows as the kernels see them, row slots a tile, the most tiles a
    launch of ``t`` rows can fill): ``t`` rounded up to the dtype's 16
    sublanes; a tile of ``_ROWS`` slots or all the rows if they are fewer;
    and ``t * min(top_k, h)`` pairs over ``h`` experts that each start a
    tile of their own, no expert with more than ``t``."""
    tp = _round_up(t, 16)
    tm = min(_ROWS, tp)
    pairs = t * min(top_k, h)
    return tp, tm, min((pairs + h * (tm - 1)) // tm, h * -(-t // tm))


def layout(gates: jax.Array, tm: int, tiles: int) -> dict:
    """Where the pairs of ``gates [T, H]`` lie: ``rank [H, 1, T]`` (a row's
    place among its expert's rows, -1 where the gate is zero) and, a tile,
    ``expert``, ``base`` (the rank of its first slot), ``count`` (its live
    slots, the first of the tile) and ``rows [tiles * tm]`` (the row of
    each slot; ``T`` where none), with ``live``, the tiles that hold a
    pair. Cumulative sums and comparisons: no sort, no scatter."""
    t, h = gates.shape
    hot = gates != 0
    seen = jnp.cumsum(hot.astype(jnp.int32), axis=0)  # [T, H], inclusive
    counts = seen[-1]
    of_expert = -(-counts // tm)
    ends = jnp.cumsum(of_expert)
    tile = jnp.arange(tiles, dtype=jnp.int32)
    expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile[:, None], axis=1), h - 1)
    base = (tile - (ends - of_expert)[expert]) * tm
    live = ends[-1]
    count = jnp.where(tile < live,
                      jnp.clip(counts[expert] - base, 0, tm), 0)
    # slot s of a tile holds its expert's row of rank base + s: the rows
    # before it are those whose inclusive count is at most that rank
    want = base[:, None] + jnp.arange(tm, dtype=jnp.int32)[None, :]
    rows = jnp.sum(seen.T[expert][:, None, :] <= want[:, :, None], axis=-1)
    return {
        "rank": jnp.where(hot, seen - 1, -1).T[:, None, :],
        "expert": expert.astype(jnp.int32),
        # a tile with no pair (only ever the first, of a launch without
        # one) picks no row
        "base": jnp.where(tile < live, base, t).astype(jnp.int32),
        "count": count.astype(jnp.int32),
        "rows": rows.reshape(-1).astype(jnp.int32),
        "live": live.astype(jnp.int32),
    }


def _gate_up_kernel(layer_ref, expert_ref, base_ref, x_ref, rank_ref,
                    gates_ref, wg_ref, wu_ref, act_ref, gate_acc, up_acc, *,
                    td, steps):
    """One tile against one block of ``D``: its rows picked out of ``x``,
    both products accumulated, and at the last block the weighed
    activation."""
    del layer_ref, expert_ref  # the index maps' own
    i, k = pl.program_id(0), pl.program_id(1)
    tm, t = act_ref.shape[0], x_ref.shape[0]
    slot = base_ref[i] + jax.lax.broadcasted_iota(jnp.int32, (tm, t), 0)
    pick = rank_ref[...] == slot  # [tm, T]: slot s holds row t
    x = x_ref[:, pl.ds(pl.multiple_of(k * td, td), td)]
    rows = jnp.dot(pick.astype(x.dtype), x,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    gate = jnp.dot(rows, wg_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(rows, wu_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == 0)
    def _():
        gate_acc[...] = gate
        up_acc[...] = up

    @pl.when(k > 0)
    def _():
        gate_acc[...] += gate
        up_acc[...] += up

    @pl.when(k == steps - 1)
    def _():
        weight = jnp.sum(jnp.where(pick, gates_ref[...], 0.0), axis=1,
                         keepdims=True)  # [tm, 1]: the pair's gate
        dtype = act_ref.dtype
        gate = gate_acc[...].astype(dtype).astype(jnp.float32)
        up = up_acc[...].astype(dtype).astype(jnp.float32)
        act_ref[...] = (jax.nn.silu(gate) * up * weight).astype(dtype)


def _down_kernel(layer_ref, expert_ref, count_ref, rows_ref, act_ref, wd_ref,
                 out_ref, acc, *, steps):
    """One tile against one block of ``F`` for one block of ``D``'s
    columns; at the last block of ``F`` each live slot's row is added to
    the output row it came from."""
    del layer_ref, expert_ref
    i, f = pl.program_id(1), pl.program_id(2)
    tm = act_ref.shape[0]

    @pl.when((i == 0) & (f == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    part = jnp.dot(act_ref[...], wd_ref[...],
                   preferred_element_type=jnp.float32)

    @pl.when(f == 0)
    def _():
        acc[...] = part

    @pl.when(f > 0)
    def _():
        acc[...] += part

    @pl.when(f == steps - 1)
    def _():
        def add(s, carry):
            row = rows_ref[i * tm + s]
            out_ref[pl.ds(row, 1), :] += acc[pl.ds(s, 1), :]
            return carry

        jax.lax.fori_loop(0, count_ref[i], add, 0)


@functools.partial(jax.jit, static_argnames=("top_k", "interpret"))
def grouped_experts_ffn(x: jax.Array, gates: jax.Array, w_gate: jax.Array,
                        w_up: jax.Array, w_down: jax.Array, layer,
                        top_k: int, interpret: bool = False) -> jax.Array:
    """``sum_h gates[:, h] * swiglu_h(x)`` over the held experts of layer
    ``layer`` of the stacks ``w_gate`` / ``w_up [L, H, D, F]``, ``w_down
    [L, H, F, D]`` (whole, as stored: a layer sliced out for a kernel would
    be copied), each expert over the rows with a gate other than zero
    alone; ``x [T, D]``, ``gates [T, H]`` float32 with at most ``min(top_k,
    H)`` of them a row. Returns ``[T, D]`` float32: a row's pairs summed,
    not yet rounded."""
    t, d = x.shape
    h, ff = w_gate.shape[1], w_gate.shape[3]
    tp, tm, tiles = plan(t, h, top_k)
    if tp != t:  # rows of zeros with no pair
        x = jnp.pad(x, ((0, tp - t), (0, 0)))
        gates = jnp.pad(gates, ((0, tp - t), (0, 0)))
    at = layout(gates, tm, tiles)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    grid_tiles = jnp.maximum(at["live"], 1)  # the output is zeroed in one
    td, tf = _block(d), _block(ff)
    dn = _columns(d)
    params = dict(vmem_limit_bytes=_VMEM_BYTES)
    act = pl.pallas_call(
        functools.partial(_gate_up_kernel, td=td, steps=d // td),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(grid_tiles, d // td),
            in_specs=[
                pl.BlockSpec((tp, d), lambda i, k, l, e, b: (0, 0)),
                pl.BlockSpec((None, 1, tp),
                             lambda i, k, l, e, b: (e[i], 0, 0)),
                pl.BlockSpec((None, 1, tp),
                             lambda i, k, l, e, b: (e[i], 0, 0)),
                pl.BlockSpec((None, None, td, ff),
                             lambda i, k, l, e, b: (l[0], e[i], k, 0)),
                pl.BlockSpec((None, None, td, ff),
                             lambda i, k, l, e, b: (l[0], e[i], k, 0)),
            ],
            out_specs=pl.BlockSpec((tm, ff), lambda i, k, l, e, b: (i, 0)),
            scratch_shapes=[pltpu.VMEM((tm, ff), jnp.float32),
                            pltpu.VMEM((tm, ff), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles * tm, ff), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), **params),
        interpret=interpret, name="experts_gate_up",
    )(layer, at["expert"], at["base"], x, at["rank"],
      gates.astype(jnp.float32).T[:, None, :], w_gate, w_up)
    out = pl.pallas_call(
        functools.partial(_down_kernel, steps=ff // tf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(d // dn, grid_tiles, ff // tf),
            in_specs=[
                pl.BlockSpec((tm, tf), lambda n, i, f, l, e, c, r: (i, f)),
                pl.BlockSpec((None, None, tf, dn),
                             lambda n, i, f, l, e, c, r: (l[0], e[i], f, n)),
            ],
            out_specs=pl.BlockSpec(
                (tp, dn), lambda n, i, f, l, e, c, r: (0, n)),
            scratch_shapes=[pltpu.VMEM((tm, dn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            **params),
        interpret=interpret, name="experts_down",
    )(layer, at["expert"], at["count"], at["rows"], act, w_down)
    return out[:t]
