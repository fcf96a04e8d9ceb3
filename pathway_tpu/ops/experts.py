"""Grouped products of a routed expert layer, as Pallas kernels for the TPU.

A decoder with routed experts (models/routed.py `experts`) sorts its
token-expert pairs by expert, so that expert e multiplies the run of rows
`offsets[e] .. offsets[e + 1]` whatever its length: no capacity, no dropped
pair. `jax.lax.ragged_dot` states that product and gives no hold on its
tiles; on a v5e its lowering ran a prefill's three products at a third of
the bf16 peak, wrote two float32 `[pairs, ff]` intermediates for an
element-wise pass to read back, and left the pair's routing weight and the
way back into token order to three more passes over a float32
`[pairs, d]` (PERF.md section 5, PR 36).

`grouped_experts` is the layer's arithmetic in two kernels:

* `expert_gate_up`: a tile of rows is loaded once, multiplied with the
  expert's `expert_gate` and `expert_up`, and `act(gate) * up` (ReLU, or
  SiLU where the experts are SwiGLU) leaves the float32 accumulators as
  `[pairs, ff]` in the rows' dtype;
* `expert_down`: that tile times the expert's `expert_down`, each row
  scaled in float32 by its pair's routing weight before it is written.

Both walk the same list of *visits* (`expert_visits`): the runs of rows
between one tile edge or group edge and the next, in row order. A visit
names one tile of rows and one expert; a tile that holds the edge of a
group is visited once for each group it holds, its rows of the other
groups masked, never padded; an expert with no pair has no visit. The
expert's matrices are the kernels' own operands, picked by the visit's
group through a scalar-prefetch index map: they are fetched once a group,
not once a tile, and nothing is copied out of a leaf.

`combine_experts` is what is left of the layer, a sum over a token's
pairs: a third kernel that fetches each token's rows by their index from
where the down kernel wrote them and writes only the sums.

models/routed.py `experts_use_kernel` says which layers run these: a
prefill's; the decode step's few pairs stay on `ragged_dot`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Rows of a tile, and of the blocks a visit's product is made in: a block
# that holds no row of the visit is skipped, so a group's edge costs a block
# and not a tile. Chosen on the chip against groups of 960-1,250 rows of
# width 2,560 and experts of 768 (PERF.md section 6, PR 36, has what else
# was tried: tiles of 256 to 1,024, blocks of 64 to 512).
_TILE = 512
_BLOCK = 128
_PARAMS = pltpu.CompilerParams(
    # in order: a tile's visits follow each other and share its result
    # block, and a step of the combine starts the next step's copies
    dimension_semantics=("arbitrary",),
    # v5e has 128 MiB; `grouped_experts` has the kernels' own sums
    vmem_limit_bytes=100 << 20,
)


def expert_tile(m: int) -> tuple[int, int]:
    """(rows of a tile, rows of a block) for `m` pairs: `_TILE` and `_BLOCK`,
    or for fewer rows than a tile the rows themselves rounded up to the 16
    of a packed bfloat16 sublane tile, as one block."""
    if m >= _TILE:
        return _TILE, _BLOCK
    tm = -(-m // 16) * 16
    return tm, tm


def expert_visits(sizes: jax.Array, m: int, tm: int):
    """The visits of `m` rows in tiles of `tm` for groups of `sizes` rows
    (int32 [groups], summing to m): (tile, group, start) with start
    [visits + 1], visit v being rows start[v] .. start[v + 1] of tile
    tile[v], all of group group[v]. Their number is fixed by the shapes,
    tiles + groups - 1: the edges are the tile starts and the inner group
    starts in row order, and where two fall on one row (a group that starts
    a tile, an expert with no pair) the visit between them is empty, names
    its successor's tile and group, and so fetches and runs nothing."""
    inner = jnp.cumsum(sizes)[:-1].astype(jnp.int32)  # groups 1.. start here
    tiles = jnp.arange(-(-m // tm), dtype=jnp.int32) * tm
    start = jnp.sort(jnp.concatenate([tiles, inner]))
    # an edge at m (trailing experts without a pair) opens no visit: it
    # keeps the last row's tile and group
    at = jnp.minimum(start, m - 1)
    group = jnp.searchsorted(inner, at, side="right").astype(jnp.int32)
    return at // tm, group, jnp.concatenate([start, jnp.full((1,), m, jnp.int32)])


def _blocks(tile_ref, start_ref, tm: int, sub: int):
    """For each block of `sub` rows of the tile of the grid step's visit:
    (its rows in the tile, whether the visit has a row among them, the mask
    [sub, 1] of the visit's rows)."""
    v = pl.program_id(0)
    lo, hi = start_ref[v], start_ref[v + 1]
    base = tile_ref[v] * tm
    out = []
    for j in range(tm // sub):
        r0 = base + j * sub
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
        out.append((
            slice(j * sub, (j + 1) * sub), (r0 < hi) & (r0 + sub > lo),
            (row >= lo) & (row < hi),
        ))
    return out


_ACTS = {"relu": lambda gate: jnp.maximum(gate, 0.0), "silu": jax.nn.silu}


def _gate_up_kernel(tile_ref, group_ref, start_ref, x_ref, wg_ref, wu_ref,
                    o_ref, *, tm: int, sub: int, act: str):
    """One visit: its rows [tm, d] against the expert's gate and up
    matrices [d, ff], `act(gate) * up` from the float32 accumulators."""
    del group_ref  # the index maps' business
    for rows, any_row, mine in _blocks(tile_ref, start_ref, tm, sub):

        @pl.when(any_row)
        def _block(rows=rows, mine=mine):
            x = x_ref[rows, :]
            gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
            up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
            hidden = (_ACTS[act](gate) * up).astype(o_ref.dtype)
            # the tile's rows of other groups keep what their own visit
            # wrote, or will be written by it
            o_ref[rows, :] = jnp.where(mine, hidden, o_ref[rows, :])


def _down_kernel(tile_ref, group_ref, start_ref, h_ref, wd_ref, s_ref, o_ref,
                 *, tm: int, sub: int):
    """One visit: its hidden rows [tm, ff] against the expert's down matrix
    [ff, d], each row scaled by its pair's weight [tm, 1] in float32, and
    written as [tm, d / 128, 128]: a row is then a whole (d / 128, 128)
    slab, which `combine_experts` can fetch by its index."""
    del group_ref
    for rows, any_row, mine in _blocks(tile_ref, start_ref, tm, sub):

        @pl.when(any_row)
        def _block(rows=rows, mine=mine):
            y = jnp.dot(
                h_ref[rows, :], wd_ref[...], preferred_element_type=jnp.float32
            ) * s_ref[rows, :]
            y = y.reshape(sub, -1, 128)
            o_ref[rows] = jnp.where(mine[:, :, None], y, o_ref[rows])


def _row_tile(v, tile_ref, group_ref, start_ref):
    return tile_ref[v], 0


def _row_slabs(v, tile_ref, group_ref, start_ref):
    return tile_ref[v], 0, 0


def _group_matrix(v, tile_ref, group_ref, start_ref):
    return group_ref[v], 0, 0


def _gate_up(visits, rows, expert_gate, expert_up, tm: int, sub: int,
             act: str, interpret: bool) -> jax.Array:
    m, d = rows.shape
    ff = expert_gate.shape[2]
    return pl.pallas_call(
        functools.partial(_gate_up_kernel, tm=tm, sub=sub, act=act),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits[0].shape[0],),
            in_specs=[
                pl.BlockSpec((tm, d), _row_tile),
                pl.BlockSpec((None, d, ff), _group_matrix),
                pl.BlockSpec((None, d, ff), _group_matrix),
            ],
            out_specs=pl.BlockSpec((tm, ff), _row_tile),
        ),
        out_shape=jax.ShapeDtypeStruct((m, ff), rows.dtype),
        compiler_params=_PARAMS,
        name="expert_gate_up",
        interpret=interpret,
    )(*visits, rows, expert_gate, expert_up)


def _down(visits, hidden, expert_down, weight, tm: int, sub: int,
          interpret: bool) -> jax.Array:
    m, ff = hidden.shape
    d = expert_down.shape[2]
    return pl.pallas_call(
        functools.partial(_down_kernel, tm=tm, sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits[0].shape[0],),
            in_specs=[
                pl.BlockSpec((tm, ff), _row_tile),
                pl.BlockSpec((None, ff, d), _group_matrix),
                pl.BlockSpec((tm, 1), _row_tile),
            ],
            out_specs=pl.BlockSpec((tm, d // 128, 128), _row_slabs),
        ),
        out_shape=jax.ShapeDtypeStruct((m, d // 128, 128), jnp.float32),
        compiler_params=_PARAMS,
        name="expert_down",
        interpret=interpret,
    )(*visits, hidden, expert_down, weight.astype(jnp.float32).reshape(m, 1))


# jitted so that the layers of one program share one trace of the kernels
# and one lowering to Mosaic (ops/attention.py `prefill_attention` has why)
@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def grouped_experts(
    rows: jax.Array,  # [pairs, d]: each pair's token row, sorted by expert
    weight: jax.Array,  # [pairs] float32: each pair's routing weight, likewise
    sizes: jax.Array,  # [experts] int32: the pairs of each expert
    expert_gate: jax.Array,  # [experts, d, ff]
    expert_up: jax.Array,  # [experts, d, ff]
    expert_down: jax.Array,  # [experts, ff, d]
    *,
    act: str = "relu",  # the gate's activation: relu (ReGLU) | silu (SwiGLU)
    interpret: bool = False,
) -> jax.Array:
    """weight x (act(rows gate_e) * (rows up_e)) down_e for every pair, e
    the expert whose run of `sizes` the pair's row lies in: float32
    [pairs, d / 128, 128] (a row as a slab of lane tiles, which
    `combine_experts` fetches whole), in the rows' order. Products in the
    rows' dtype with float32
    accumulation, the gated product taken in float32 and rounded to the
    rows' dtype between the two kernels, the weight applied in float32: as
    models/routed.py `experts` states them over `ragged_dot`. Every
    pair is computed, however many an expert has.

    The matrices must be the rows' dtype: they are read where they lie.
    d and ff must be multiples of 128 (lane tiles). Grid (visits,), see
    `expert_visits`; VMEM of a step at (512, 2560, 768) bfloat16: two
    matrices of 3.9 MB double-buffered (15.7 MB), the rows' tile 2 x 2.6
    MB, the result's 2 x 0.8 MB and a block's float32 accumulators; the
    down kernel's matrix 2 x 3.9 MB and its float32 result tile 2 x 6.3 MB
    (20 lane tiles a row lie in 24 sublanes)."""
    m, d = rows.shape
    ff = expert_gate.shape[2]
    if d % 128 or ff % 128:
        raise ValueError(f"grouped_experts needs widths of a multiple of 128 "
                         f"lanes, got {d} and {ff}")
    for name, leaf in (("expert_gate", expert_gate), ("expert_up", expert_up),
                       ("expert_down", expert_down)):
        if leaf.dtype != rows.dtype:
            raise ValueError(f"{name} is {leaf.dtype}, the rows {rows.dtype}: "
                             f"the kernel reads a leaf where it lies")
    tm, sub = expert_tile(m)
    visits = expert_visits(sizes.astype(jnp.int32), m, tm)
    hidden = _gate_up(
        visits, rows, expert_gate, expert_up, tm, sub, act, interpret
    )
    return _down(visits, hidden, expert_down, weight, tm, sub, interpret)


# ------------------------------------------------------------- the combine
#
# What is left of the layer after `grouped_experts` is a sum over a token's
# pairs, whose rows lie by expert. Written in XLA it is a gather of every
# pair's float32 row into token order (a read and a write of the whole
# [pairs, d]) and a sum that reads it again. The kernel below fetches a
# token's rows itself, by row index, and writes only the sum. A row of a
# tiled [pairs, d] array is a sublane of d / 128 tiles and no copy can name
# it; of [pairs, d / 128, 128], as the down kernel writes it, it is a whole
# slab along an axis that is not tiled.

_COMBINE_TOKENS = 64  # tokens a grid step sums: k x 64 row copies in flight


def _combine_kernel(back_ref, y_ref, o_ref, buf, sem, *, k: int, tt: int,
                    tokens: int):
    """One grid step: the k rows of each of `tt` tokens, fetched by index
    from y [pairs, d / 128, 128] where it lies into buf [2, k, tt, d / 128,
    128], summed in float32. The next step's rows are on their way while
    this one's are summed."""
    i, n = pl.program_id(0), pl.num_programs(0)

    def rows(step, slot, act):
        def token(r, carry):
            # a last tile that hangs over the tokens fetches the last one's
            t = jnp.minimum(step * tt + r, tokens - 1)
            for j in range(k):
                act(pltpu.make_async_copy(
                    y_ref.at[pl.ds(back_ref[j * tokens + t], 1)],
                    buf.at[slot, j, pl.ds(r, 1)], sem.at[slot],
                ))
            return carry

        jax.lax.fori_loop(0, tt, token, 0)

    @pl.when(i == 0)
    def _first():
        rows(0, 0, lambda copy: copy.start())

    @pl.when(i + 1 < n)
    def _next():
        rows(i + 1, (i + 1) % 2, lambda copy: copy.start())

    slot = i % 2
    rows(i, slot, lambda copy: copy.wait())
    total = buf[slot, 0]
    for j in range(1, k):
        total = total + buf[slot, j]
    o_ref[...] = total.reshape(tt, -1).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def combine_experts(
    y: jax.Array,  # [pairs, d / 128, 128] float32: `grouped_experts`' result
    back: jax.Array,  # [k, tokens] int32: the row of each token's j-th pair
    dtype,  # of the result
    *,
    interpret: bool = False,
) -> jax.Array:
    """sum_j y[back[j, t]] for every token t, in float32, as `dtype`:
    [tokens, d]. The rows are read where they lie, one
    copy a row, and nothing but the sums is written. Grid (tokens / 64,);
    VMEM: two buffers of k x 64 rows (4.7 MB each at k 6, d 2560)."""
    k, tokens = back.shape
    d = y.shape[1] * y.shape[2]
    tt = min(_COMBINE_TOKENS, -(-tokens // 16) * 16)
    return pl.pallas_call(
        functools.partial(_combine_kernel, k=k, tt=tt, tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-tokens // tt),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tt, d), lambda i, back_ref: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, k, tt, *y.shape[1:]), y.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        compiler_params=_PARAMS,
        name="expert_combine",
        interpret=interpret,
    )(back.astype(jnp.int32).reshape(-1), y)
