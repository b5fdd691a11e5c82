"""Rotary embedding in interleaved pairs, IN PLACE, as one pass from the
layout a projection writes to the layout the attention core reads:
`y = x cos + swapped(x) sin` in float32, then the cast, where `swapped`
exchanges the two dimensions of every pair (2i, 2i + 1) of a head and the
tables are expanded on the host to a head's width (`tables`: 1 and 0 on the
dimensions that do not turn, -sin on a pair's first, +sin on its second).
Every dimension stays where it is within its head: no stride-2 pick, no
concatenation, no array but the operand and the result
(DeepSeek's own code, and models/deepseek_v2.py until PR 37, leave the
pairs' first halves, then their second: the same numbers in another order).

  layout: x [W, T, n x d] token-major, `n` heads of `d` dimensions side by
    side in a row; the result [W, n, T, d] head-major, in `dtype`.
  the compiler's form (`plain`): two `roll`s, a `select`, a transpose. XLA
    for the TPU does not fuse a roll along the minor dimension into its
    reader: it writes both shifted copies out (two `f32[1, 1,024, 128,
    191]` a pass at DeepSeek-V2's published size, compiled for a described
    v5e), and a reshape of rows of `n x 192` into heads of 192 is no
    bitcast under the chip's 128-lane tiles: two more copies. So at shapes
    the kernel takes it is
  the kernels: a block of whole rows in VMEM, walked in chunks of `period`
    lanes (the least run of whole heads that is whole lane tiles: 384 = 2
    heads of 192), a lane tile at a time. `_to_heads`: a tile in which
    something turns (`turning`, read off the tables on the host) has its
    neighbours fetched by two lane rotations on the XLU, selected by the
    lane's parity, and is multiplied by the block's tables; then each
    head of the chunk is written as that head's rows, a head that does
    not start on a tile put together from two tiles rotated down.
    `_from_heads`, the transpose: a chunk's tiles are put together from
    its heads' rows (a last, narrower piece through a scratch row a tile
    wide; rotated up where the head does not start on a tile), turned
    BACK (the caller negates the sine) and written. The operand is read
    once and the result written once, whatever the layout: 150 MB a call
    at the published q, 0.18 ms at the HBM's peak, 0.26-0.28 measured.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a kernel's buffers may take of VMEM: one rule for the repo's kernels
from biscotti_tpu.ops.grouped_matmul import _VMEM_BUFFERS

_LANES = 128
ROWS = (64, 32, 16)  # rows of a block, in the order `rows` tries them
# chunks a step of a kernel's loop: at the published q on the v5e, 32 rows a
# block, one call took 0.487 | 0.509 ms (to | from heads) a chunk a step,
# 0.321 | 0.337 at 2, 0.264 | 0.284 at 4, 0.296 | 0.307 at 8 and 0.303 |
# 0.321 with all 64 unrolled (PERF.md section 6, PR 37)
UNROLL = 4


def tables(cos, sin, width: int):
    """(cos, sin) float32[T, width] of a head of `width` dimensions whose
    LAST 2 x cos.shape[1] turn, pair (2i, 2i + 1) by angle i: from (cos,
    sin) float32[T, pairs] on the host."""
    cos, sin = np.asarray(cos, np.float32), np.asarray(sin, np.float32)
    still = ((0, 0), (width - 2 * cos.shape[1], 0))
    return (np.pad(np.repeat(cos, 2, axis=1), still, constant_values=1.0),
            np.pad(np.stack([-sin, sin], -1).reshape(len(sin), -1), still))


def plain(x, cos, sin, dtype):
    """The compiler's form: x [W, T, n x d] in any float type, cos and sin
    float32[T, d] from `tables`; `dtype`[W, n, T, d]."""
    d = cos.shape[1]
    x = x.reshape(x.shape[:-1] + (-1, d)).astype(jnp.float32)
    even = np.arange(d) % 2 == 0
    swapped = jnp.where(even, jnp.roll(x, -1, -1), jnp.roll(x, 1, -1))
    y = x * cos[:, None] + swapped * sin[:, None]
    return y.astype(dtype).transpose(0, 2, 1, 3)


def _plain_back(dy, cos, sin, dtype):
    """The other way: dy [W, n, T, d] turned by (cos, sin), `dtype`[W, T,
    n x d]. With the sine negated, `plain`'s transpose."""
    w, n, t, d = dy.shape
    return plain(dy.transpose(0, 2, 1, 3).reshape(w, t, n * d), cos, sin,
                 dtype).transpose(0, 2, 1, 3).reshape(w, t, n * d)


def rows(t: int, lanes: int, d: int, size: int, size_heads: int):
    """Rows of the kernels' block on windows of `t` rows of `lanes`
    dimensions in heads of `d`, `size` the bytes of an element token-major
    and `size_heads` head-major (where a head's rows take whole lane tiles
    in VMEM), or None where the kernels do not take the shape: heads that
    never come to whole lane tiles within a row, or a window that is no
    whole number of blocks."""
    period = math.lcm(d, _LANES)
    if d % 2 or lanes % period:
        return None
    wide = lanes // d * (-(-d // _LANES) * _LANES)
    return next((r for r in ROWS if t % r == 0
                 and 2 * r * (lanes * size + wide * size_heads
                              + 2 * 4 * period) + 4 * r * _LANES
                 <= _VMEM_BUFFERS), None)


def _turned(column, cos, sin, even):
    """x cos + swapped(x) sin of one lane tile x float32[rows, 128]: a
    pair's other dimension is the lane after an even one, before an odd one
    (128 is even: a pair never spans two tiles, the wrap is never read)."""
    swapped = jnp.where(even, pltpu.roll(column, _LANES - 1, 1),
                        pltpu.roll(column, 1, 1))
    return column * cos + swapped * sin


def _walk(chunks: int, body):
    """`body(c)` of every chunk c < chunks, UNROLL of them a step of the
    loop: a chunk's chain of load, rotate, select, multiply and store is
    latency a step, which chunks side by side hide."""
    step = math.gcd(chunks, UNROLL)

    def some(at, _):
        for c in range(step):
            body(at * step + c)

    jax.lax.fori_loop(0, chunks // step, some, None)


def _turning(cos_ref, sin_ref, turning):
    """{lane tile of a chunk: the block's (cos, sin) of it} of the tiles in
    which something turns."""
    return {i: (cos_ref[:, i * _LANES:(i + 1) * _LANES],
                sin_ref[:, i * _LANES:(i + 1) * _LANES]) for i in turning}


def _tiles(width: int):
    """[(lane tile, its width)] of a row of `width` lanes from lane 0."""
    return [(k, min(_LANES, width - k * _LANES))
            for k in range(-(-width // _LANES))]


def _to_heads(x_ref, cos_ref, sin_ref, out_ref, *, turning):
    period, d = cos_ref.shape[1], out_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (x_ref.shape[0], _LANES), 1)
    even, table = lane % 2 == 0, _turning(cos_ref, sin_ref, turning)

    def chunk(c):
        tiles = []
        for i in range(period // _LANES):
            at = pl.ds(pl.multiple_of(c * period + i * _LANES, _LANES), _LANES)
            x = x_ref[:, at].astype(jnp.float32)
            tiles.append(_turned(x, *table[i], even) if i in table else x)
        for j in range(period // d):  # a head's lanes, down to lane 0
            first, shift = divmod(j * d, _LANES)
            if shift:  # from two tiles, each rotated down by `shift`
                low = [pltpu.roll(t, _LANES - shift, 1) for t in tiles[first:]]
                head = [jnp.where(lane < _LANES - shift, a, b)
                        for a, b in zip(low, low[1:])] + low[-1:]
            else:
                head = tiles[first:]
            for k, width in _tiles(d):
                out_ref[c * (period // d) + j, :,
                        k * _LANES:k * _LANES + width] = head[k][
                            :, :width].astype(out_ref.dtype)

    _walk(x_ref.shape[1] // period, chunk)


def _from_heads(dy_ref, cos_ref, sin_ref, out_ref, row, *, turning):
    period, d = cos_ref.shape[1], dy_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (out_ref.shape[0], _LANES), 1)
    even, table = lane % 2 == 0, _turning(cos_ref, sin_ref, turning)

    def chunk(c):
        tiles = [None] * (period // _LANES)

        def put(i, a, b, piece):  # lanes [a, b) of tile i
            tiles[i] = piece if tiles[i] is None else jnp.where(
                (lane >= a) & (lane < b), piece, tiles[i])

        for j in range(period // d):  # a head's rows, up to its lanes
            first, shift = divmod(j * d, _LANES)
            for k, width in _tiles(d):
                piece = dy_ref[c * (period // d) + j, :,
                               k * _LANES:k * _LANES + width].astype(
                                   jnp.float32)
                if width < _LANES:  # through a scratch row, a tile wide
                    row[:, :width] = piece
                    piece = row[...]
                if shift:
                    piece = pltpu.roll(piece, shift, 1)
                put(first + k, shift, min(shift + width, _LANES), piece)
                if shift + width > _LANES:
                    put(first + k + 1, 0, shift + width - _LANES, piece)
        for i, x in enumerate(tiles):
            at = pl.ds(pl.multiple_of(c * period + i * _LANES, _LANES), _LANES)
            out_ref[:, at] = (_turned(x, *table[i], even)
                              if i in table else x).astype(out_ref.dtype)

    _walk(out_ref.shape[1] // period, chunk)


def _specs(n: int, d: int, block: int, period: int):
    """BlockSpecs of (a block of whole token-major rows, the same rows of
    every head, the block's tables) on the grid (W, row block)."""
    return (pl.BlockSpec((None, block, n * d), lambda w, i: (w, i, 0)),
            pl.BlockSpec((None, n, block, d), lambda w, i: (w, 0, i, 0)),
            pl.BlockSpec((block, period), lambda w, i: (i, 0)))


def _call(interpret, x, *, cos, sin, d, dtype, block):
    """`_to_heads` of x [W, T, n x d], `_from_heads` of x [W, n, T, d]: in
    `dtype`, in the other layout."""
    w, t = x.shape[0], x.shape[-2]
    n = x.size // (w * t * d)
    rows_, heads, table = _specs(n, d, block, cos.shape[1])
    to_heads = x.ndim == 3
    # the lane tiles of a chunk in which some dimension turns at all
    turning = tuple(i for i in range(cos.shape[1] // _LANES)
                    if sin[:, i * _LANES:(i + 1) * _LANES].any())
    # Mosaic has no 64-bit types: traced with x64 off, as the repo's other
    # kernels are
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_to_heads if to_heads else _from_heads, turning=turning),
            grid=(w, t // block),
            in_specs=[rows_ if to_heads else heads, table, table],
            out_specs=heads if to_heads else rows_,
            out_shape=jax.ShapeDtypeStruct(
                (w, n, t, d) if to_heads else (w, t, n * d), dtype),
            scratch_shapes=[] if to_heads else [
                pltpu.VMEM((block, _LANES), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="rotary_to_heads" if to_heads else "rotary_from_heads",
        )(x, jnp.asarray(cos), jnp.asarray(sin))


def _run(x, cos, sin, dtype):
    """`plain(x, cos, sin, dtype)` of x [W, T, n x d], `_plain_back` of x
    [W, n, T, d], by the kernels where `rows` takes the shape."""
    dtype, d = jnp.dtype(dtype), cos.shape[1]
    sizes = (x.dtype.itemsize, dtype.itemsize)
    block = rows(x.shape[-2], x.size // (x.shape[0] * x.shape[-2]), d,
                 *(sizes if x.ndim == 3 else sizes[::-1]))
    if block is None:
        return (plain if x.ndim == 3 else _plain_back)(x, cos, sin, dtype)
    # a table a chunk wide: the heads of a chunk side by side
    cos, sin = (np.tile(a, (1, math.lcm(d, _LANES) // d)) for a in (cos, sin))
    call = partial(_call, cos=cos, sin=sin, d=d, dtype=dtype, block=block)
    # the platform being LOWERED FOR picks the branch (ops/attention.py)
    return jax.lax.platform_dependent(
        x, tpu=partial(call, False), default=partial(call, True))


def turn(x, cos, sin, dtype):
    """`dtype`[W, n, T, d]: x [W, T, n x d] with every head's pairs turned
    (`tables` says which and by what: cos, sin float32[T, d]), in float32,
    then cast, head-major. One algorithm, the kernels or the compiler's
    form by the shapes alone; its transpose is the turn back of the
    cotangent, into x's type and layout."""
    back = x.dtype

    @jax.custom_vjp
    def forward(x):
        return _run(x, cos, sin, dtype)

    forward.defvjp(lambda x: (forward(x), None),
                   lambda _, dy: (_run(dy, cos, -sin, back),))
    return forward(x)
