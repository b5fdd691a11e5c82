"""A grouped matrix product as a Pallas TPU kernel, in row tiles that fit
the groups it gets.

`out[r] = xs[r] @ w[g]` for the rows `r` of group `g`: the rows sorted by
group, `sizes[g]` of them each, those past `sum(sizes)` in no group and
their result exact zeros (what `jax.lax.ragged_dot` gives them too). The
sparse-expert layer (ops/moe.py) sends it 64 groups of about 120 rows: the
compiler's own `ragged-dot` walks those in row tiles of 512, one visit for
every (group, tile) pair, and so computes six times the rows it was given
(PERF.md section 6, PR 28). Here the row tile is an argument, chosen by the
caller from the rows a group it expects (`row_tile`):

  grid (column tile j, step s), s innermost; a step is one of
    - a VISIT of (group g, row tile t), in the sorted order, so one group's
      visits follow each other on one weight block [K, tn] in VMEM: the
      whole K in one MXU pass (no accumulator across steps), the rows of
      the tile that are g's kept, the others left as they were (zeros on
      the tile's first visit);
    - a TAIL tile past the last group: zeros, no product, no operand read;
    - nothing: the grid is static, `C/tm + E - 1` steps, the most a
      schedule can need, and what is left over repeats the last step's
      blocks, so it moves no data.
  The schedule (group, tile and kind of every step) is computed from
  `sizes` outside the kernel, E + C/tm integers, and prefetched to SMEM.

  The weights stay in HBM and the kernel copies them itself, two buffers:
  a group's first visit starts the copy of the NEXT group's block, so that
  copy (3 MB a column tile at the cell's shapes) runs under all the visits
  of this group and not under its last one alone, as the pipeline of a
  BlockSpec would have it: at column tiles of 1,024 a call alone took 0.76
  ms against 0.92 (PERF.md section 6, PR 28).

The backward is the same kernel on the weights read transposed (no
transposed copy); no cotangent of the weights is ever formed: the experts
are frozen.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_TILES = (128, 256, 512)
COMPILER_ROW_TILE = 512  # read from the compiled `ragged-dot`'s tile tables
# what the kernel's buffers may take of VMEM: the compiler's default scoped
# limit (16 MiB) less room for the kernel's own temporaries. A kernel that
# is given more (`vmem_limit_bytes`) takes it from the fusions of the whole
# program: at 24 MiB and column tiles of 1,024 this kernel ran 21 ms a
# round faster and the attention's fusions 21 ms slower (PERF.md section 6,
# PR 28)
_VMEM_BUFFERS = 12 << 20


def row_tile(rows_a_group: float) -> int:
    """The smallest row tile that holds the rows a group is expected to
    have, the largest where none does."""
    return next((t for t in ROW_TILES if t >= rows_a_group), ROW_TILES[-1])


def _buffers(tm: int, k: int, tn: int, size: int) -> int:
    """Bytes of VMEM: the two weight blocks, two buffers each of the rows'
    and the result's blocks (the pipeline's), the product and its mask."""
    return 2 * (k * tn * size + tm * k * size + tm * tn * 4) + 2 * tm * tn * 4


def column_tile(c: int, k: int, n: int, dtype, tm: int):
    """The widest column tile whose buffers fit VMEM with the whole K in one
    block, or None where the kernel does not take the shape: rows not a
    multiple of the row tile, K or N not of 128, another type of weights,
    or a K too long for any tile."""
    if (c % tm or k % 128 or n % 128
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)):
        return None
    size = jnp.dtype(dtype).itemsize
    return next((tn for tn in (1024, 512, 256, 128) if n % tn == 0
                 and _buffers(tm, k, tn, size) <= _VMEM_BUFFERS), None)


def _walk(sizes: jax.Array, tm: int):
    """Groups of `sizes` rows laid end to end from row 0, in row tiles of
    `tm`: (each group's end row, its first tile, the tiles it has rows in),
    int32[E] each."""
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    first = (ends - sizes) // tm
    return ends, first, jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)


def tile_visits(sizes: jax.Array, tm: int) -> jax.Array:
    """(group, row tile) pairs a walk in row tiles of `tm` visits: int32."""
    return jnp.sum(_walk(sizes, tm)[2], dtype=jnp.int32)


def schedule(sizes: jax.Array, c: int, tm: int):
    """The kernel's walk over `sizes` int32[E] in a buffer of `c` rows:
    (group, row tile read, row tile written) of every step, int32[steps]
    each; the groups' offsets int32[E + 1]; the number of visits int32[1];
    and, a group, the next group that has rows (-1: none) and which of the
    two weight buffers is its own, int32[E] each. Steps past the visits
    write the tail's tiles, then repeat."""
    e, tiles = sizes.shape[0], c // tm
    step = jnp.arange(tiles + e - 1, dtype=jnp.int32)
    ends, first, count = _walk(sizes, tm)
    upto = jnp.cumsum(count, dtype=jnp.int32)
    visits = upto[-1]
    # the group whose visits hold step s: as many groups end at or before it
    group = jnp.minimum(jnp.sum(step[:, None] >= upto[None], axis=1,
                                dtype=jnp.int32), e - 1)
    tile = jnp.minimum(first[group] + step - (upto[group] - count[group]),
                       tiles - 1)
    last = jnp.maximum(visits - 1, 0)
    visit = step < visits
    tail = jnp.minimum((ends[-1] + tm - 1) // tm + step - visits, tiles - 1)
    ids = jnp.arange(e, dtype=jnp.int32)
    later = jnp.min(jnp.where((sizes > 0)[None] & (ids[None] > ids[:, None]),
                              ids[None], e), axis=1)
    return (jnp.where(visit, group, group[last]),
            jnp.where(visit, tile, tile[last]),
            jnp.where(visit, tile, tail),
            jnp.concatenate([jnp.zeros((1,), jnp.int32), ends]),
            visits[None],
            jnp.where(later < e, later, -1),
            (jnp.cumsum(sizes > 0, dtype=jnp.int32) - 1) % 2)


def _kernel(group_ref, _, tile_ref, offset_ref, visits_ref, later_ref,
            buffer_ref, xs_ref, w_hbm, out_ref, w_vmem, arrived, *, tm: int,
            tn: int, transposed: bool):
    j, s = pl.program_id(0), pl.program_id(1)
    tile, g = tile_ref[s], group_ref[s]
    before = jnp.maximum(s - 1, 0)
    fresh = (s == 0) | (tile_ref[before] != tile)
    visit = s < visits_ref[0]
    mine = buffer_ref[g]

    def block(g, buffer):
        """The copy of group g's weight block of this column tile."""
        at = pl.ds(j * tn, tn)
        return pltpu.make_async_copy(
            w_hbm.at[g, at, :] if transposed else w_hbm.at[g, :, at],
            w_vmem.at[buffer], arrived.at[buffer])

    @pl.when(visit & (s == 0))
    def _():
        block(g, mine).start()

    @pl.when(visit & ((s == 0) | (group_ref[before] != g)))
    def _():  # a group's first visit: the other buffer's visits are done
        @pl.when(later_ref[g] >= 0)
        def _():
            block(later_ref[g], 1 - mine).start()

        block(g, mine).wait()

    @pl.when(visit)
    def _():
        row = tile * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                   out_ref.shape, 0)
        inside = (row >= offset_ref[g]) & (row < offset_ref[g + 1])
        contract = (((1,), (1 if transposed else 0,)), ((), ()))
        product = jax.lax.dot_general(xs_ref[...], w_vmem[mine], contract,
                                      preferred_element_type=jnp.float32)
        product = jnp.where(inside, product, 0.0).astype(out_ref.dtype)

        @pl.when(fresh)
        def _():
            out_ref[...] = product

        @pl.when(jnp.logical_not(fresh))
        def _():  # the rows of other groups are zeros in `product`
            out_ref[...] += product

    @pl.when(jnp.logical_not(visit) & fresh)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _call(interpret, *operands, tm, tn, transposed, out_dtype):
    *plan, xs, w = operands
    c, k = xs.shape
    n = w.shape[1] if transposed else w.shape[2]
    # Mosaic has no 64-bit types: traced with x64 off, as the Krum kernel
    # is (ops/krum_pallas.py); every operand is 32 bits or narrower already
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_kernel, tm=tm, tn=tn, transposed=transposed),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(plan),
                grid=(n // tn, plan[0].shape[0]),
                in_specs=[pl.BlockSpec((tm, k),
                                       lambda j, s, g, t, *_: (t[s], 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((tm, tn),
                                       lambda j, s, g, t, o, *_: (o[s], j)),
                scratch_shapes=[
                    pltpu.VMEM((2, tn, k) if transposed else (2, k, tn),
                               w.dtype),
                    pltpu.SemaphoreType.DMA((2,))]),
            out_shape=jax.ShapeDtypeStruct((c, n), out_dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="grouped_matmul",
        )(*plan, xs, w)


# jitted so that a program traces each shape of it once, however many
# layers, passes and `lax.cond` sides call it (a round has 72 calls of 12
# shapes, and a trace of one takes the host 0.1 s)
@partial(jax.jit, static_argnames=("tm", "transposed", "out_dtype"))
def _run(xs, w, sizes, tm, transposed, out_dtype):
    k = xs.shape[1]
    n = w.shape[1] if transposed else w.shape[2]
    tn = column_tile(xs.shape[0], k, n, w.dtype, tm)
    assert tn is not None, (xs.shape, w.shape, tm)
    with jax.enable_x64(False):
        plan = schedule(sizes.astype(jnp.int32), xs.shape[0], tm)
    call = partial(_call, tm=tm, tn=tn, transposed=transposed,
                   out_dtype=out_dtype)
    # the platform being LOWERED FOR picks the branch, so an ahead-of-time
    # compile for a TPU from a CPU host lowers through Mosaic; interpret
    # mode exists for the JAX_PLATFORMS=cpu tests
    return jax.lax.platform_dependent(
        *plan, xs, w, tpu=partial(call, False), default=partial(call, True))


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped(xs, w, sizes, tm):
    """float32[C, N]: `xs[r] @ w[g]` for the rows r of group g, zeros for
    the rows past the groups. xs [C, K] in the weights' type, w [E, K, N],
    sizes int32[E], sum(sizes) <= C; `tm` one of ROW_TILES, and
    `column_tile(C, K, N, w.dtype, tm)` and `column_tile(C, N, K, ...)` (the
    backward's) not None. float32 accumulation."""
    return _run(xs, w, sizes, tm, False, jnp.float32)


def _grouped_fwd(xs, w, sizes, tm):
    return grouped(xs, w, sizes, tm), (w, sizes)


def _grouped_bwd(tm, res, g):
    w, sizes = res
    # the rows' cotangent in their own type, written by the kernel; none
    # for the weights: they are frozen, and [E, K, N] is never formed
    return _run(g.astype(w.dtype), w, sizes, tm, True, w.dtype), None, None


grouped.defvjp(_grouped_fwd, _grouped_bwd)
