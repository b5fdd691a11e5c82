"""The attention core `softmax(scale q k^T + mask) v` as one fused,
blocked Pallas TPU kernel a call, forward and backward: the scores live a
(query block, key block) tile at a time in the chip's own memory, under a
running maximum and sum, and no array of their size [heads, T, T] is ever
written to HBM. A pair of blocks the mask hides entirely is never visited.

The `einsum` form (`plain`, what models/laguna.py ran before PR 30) makes
the scores of a block of 3 windows in float32, [3, 8, 9, 1,024, 1,024] =
906 MB, and masks, soft-maxes, casts and multiplies them, each a pass over
HBM: 2.9 ms a call forward and 10.4 with its backward at 72 heads, where
this kernel takes 1.0 and 3.1 (PERF.md section 6, PR 30).

  layout: the grouped-query one the model has. q [W, kv, G, T, d] (G query
    heads share a key/value head), k [W, kv, T, d], v [W, kv, T, e]; the
    result float32[W, kv, G, T, e]. Windows never meet: the grid walks W.
    The scores' width d and the values' e are each their own: Laguna's
    heads 128 | 128 with G = 6 or 9, DeepSeek-V2's latent attention 192 |
    128 with G = 1, Granite-4.0-H's 64 | 64 with G = 4. A d of a lane
    tile and a half is contracted as it is: on the v5e a forward call of
    3 x 128 heads took 3.48 ms and with its backward 9.20, zero-padded to
    256 outside the kernel 4.70 and 10.42, the `einsum` form 6.18 and
    20.26 (eval/eval_attention.py --layers mla; PERF.md section 6, PR 31).
    `scale` multiplies the scores: 1 / sqrt(d) unless the caller has its
    own (DeepSeek-V2's carries YaRN's m^2).
  a SHARED key part (optional; PR 37): `shared` [W, 1, T, r], the part of
    every head's key that all heads have alike (DeepSeek-V2's ONE rotary
    key, r = 64). k is then [W, kv, T, d - r], q stays [W, kv, G, T, d]
    and the scores are scale (q[..., :d - r] k^T + q[..., d - r:]
    shared^T): the same products, and no array holds [k | shared]. The
    part's block ignores the head index, so it is fetched once a window;
    q's block is cut at k's width (lane 128: a tile boundary); dq is
    written in its two lane ranges; the part's cotangent adds up over the
    heads AND the query blocks in float32 scratch and is written once a
    window (that call's head axis is `arbitrary`), cast once, where a
    broadcast key's would be cast a head and summed afterwards. A call
    WITHOUT the part has the operands, the kernels and the lowered text it
    had before the part existed (tests/test_tpu_lowering.py pins them).
  mask: query i sees key j where `j <= i` and `i - j < window`; a window of
    T or more is the causal mask. So the key blocks a query block visits
    are a RANGE computed from its number (`key_blocks`): program-id
    arithmetic, no table.
  grid (W, kv, query block); a step holds its key/value head's whole k and
    v in VMEM (T x d each: 256 KB at the published size, fetched once a
    head, not once a query block) and, a query head of the group, walks
    the visited key blocks:
      forward   s = scale q k^T masked; m, l, acc the running maximum,
                sum and unnormalised result; out = acc / l, and the rows'
                log-sum-exp m + log l kept for the backward;
      backward  ONE kernel for dq, dk and dv: the scores again, transposed
                (keys down, queries across: the rows' log-sum-exp and
                `sum(out * dout)` enter as lane-major rows), p = exp(s -
                lse), dv += p do, ds = p (v do^T - di), dk += ds q, dq +=
                ds^T k, the scale of ds applied to dq's and
                dk's sums, once a row. dk and dv add up over the group's heads
                and the query blocks in float32 scratch and are written
                once a key/value head.
  the same arithmetic as `plain`: operands in their own type (bfloat16 at
    the published size), products accumulated in float32, the scale on
    the float32 scores, max, exp and sums in float32, the probabilities
    cast to the operands' type only for the product with v. A skipped
    block is one whose every probability is exactly 0 in `plain`.

VMEM stays inside the compiler's default: `blocks` takes the first block
pair whose buffers fit `_VMEM_BUFFERS`, as ops/grouped_matmul.py's
`column_tile` does (a kernel given more takes it from the fusions of the
whole program: PERF.md section 6, PR 28). The order is the chip's: a step
of the walk costs more than the pairs a finer block would skip, so key
blocks of 512 (three quarters of the pairs visited) run twice as fast as
key blocks of 128 (half of them), and the forward wants its queries DOWN
(p the streamed operand of the product with v, not the held one).
"""

from __future__ import annotations

import math
import operator
from functools import partial, reduce

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a kernel's buffers may take of VMEM: one rule for the repo's kernels
from biscotti_tpu.ops.grouped_matmul import _VMEM_BUFFERS

# (query block, key block), in the order `blocks` tries them: fastest first
# on the v5e (eval/eval_attention.py)
BLOCKS = ((256, 512), (256, 256), (256, 128), (128, 128))
_LANES = 128
# a hidden score: finite, so that a row whose every key of a block is
# hidden computes exp(0), not exp(-inf + inf); the row's diagonal block
# then scales that away by exp(_HIDDEN - m) = 0 exactly
_HIDDEN = -0.7 * float(np.finfo(np.float32).max)
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def plain(q, k, v, window: int, scale=None, shared=None, sink=None):
    """The `einsum` form: q [W, kv, G, T, d], k [W, kv, T, d], v [W, kv, T,
    e] in one type; float32[W, kv, G, T, e]. Makes the scores [W, kv, G, T,
    T]; `scale` multiplies them (None: 1 / sqrt(d)). With `shared` [W, 1,
    T, r], a key part every head has alike, k is [W, kv, T, d - r] and
    q's last r dimensions are contracted with `shared`. With `sink` [kv,
    G], a float a query head, the softmax has one more column, the sink's
    (unscaled, seen by every query), and that column has no value."""
    t, d = q.shape[-2:]
    own = k.shape[-1]
    scores = jnp.einsum("wgqtd,wgsd->wgqts",
                        q if shared is None else q[..., :own], k,
                        preferred_element_type=jnp.float32)
    if shared is not None:
        scores += jnp.einsum("wgqtd,wsd->wgqts", q[..., own:], shared[:, 0],
                             preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d) if scale is None else scores * scale
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) if window >= t else ((j <= i) & (i - j < window))
    if sink is None:
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, :, None, None],
            scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(jnp.concatenate(
            [jnp.where(seen, scores, -jnp.inf), column], axis=-1),
            axis=-1)[..., :-1]
    return jnp.einsum("wgqts,wgse->wgqte", probs.astype(q.dtype), v,
                      preferred_element_type=jnp.float32)


def key_blocks(iq, bq: int, bk: int, window: int, largest=max):
    """(first, last) of the key blocks of `bk` that hold a key some query
    of query block `iq` (of `bq`) sees; every block between holds one too.
    `iq` an integer (the host) or, with `largest=jnp.maximum`, a traced
    int32 (the kernel): the same arithmetic on both."""
    return (largest(iq * bq - (window - 1), 0) // bk,
            (iq * bq + bq - 1) // bk)


def visited(t: int, window: int, bq: int, bk: int):
    """The (query block, key block) pairs a call visits, in its order."""
    pairs = []
    for iq in range(t // bq):
        first, last = key_blocks(iq, bq, bk, window)
        pairs += [(iq, j) for j in range(first, last + 1)]
    return pairs


def _buffers(g: int, t: int, d: int, bq: int, bk: int, size: int,
             e: int = None, shared: int = 0) -> int:
    """Bytes of VMEM the backward (the larger of the two) holds: two
    buffers each of q's and dq's blocks (`d` wide, the scores' width), of
    the result's cotangent (float32, `e` wide, the values' width; None: d),
    of k, dk and v, dv (e) whole and of the two rows' statistics; dk's
    and dv's float32 sums; six tiles of the scores' size. k and dk are d
    wide less the `shared` dimensions of a key part all heads have alike,
    which is held like k: itself, its cotangent and that cotangent's
    float32 sum. Every width in whole lane tiles."""
    rows, e = g * bq, _padded(d if e is None else e)
    keys, d = _padded(d - shared) + _padded(shared), _padded(d)
    return (2 * rows * (2 * d * size + 4 * e) + 2 * 2 * t * (keys + e) * size
            + 2 * 2 * 4 * rows + 4 * t * (keys + e) + 6 * 4 * bq * bk)


def _padded(d: int) -> int:
    """`d` in whole lane tiles: what a last axis of d takes in VMEM."""
    return -(-d // _LANES) * _LANES


def blocks(g: int, t: int, d: int, dtype, e: int = None, shared: int = 0):
    """(query block, key block) of the kernel for `g` query heads a
    key/value head on windows of `t`, scores that contract `d` and values
    of `e` (None: d), `shared` of the d with a key part all heads have
    alike (0: every key is its head's own), or None where the kernel does
    not take the shape: a score, a shared or a value width not of 64 (half
    a lane tile: Mosaic takes whole tiles and one half, as they are;
    Granite's heads are 64 | 64), a window that is no whole number of
    blocks, another type, or a key/value head too long to hold whole."""
    e = d if e is None else e
    if (e % (_LANES // 2) or d % (_LANES // 2) or shared % (_LANES // 2)
            or jnp.dtype(dtype) not in (jnp.bfloat16, jnp.float32)):
        return None
    size = jnp.dtype(dtype).itemsize
    return next(((bq, bk) for bq, bk in BLOCKS if t % bq == 0 and t % bk == 0
                 and _buffers(g, t, d, bq, bk, size, e, shared)
                 <= _VMEM_BUFFERS), None)


def block_share(t: int, window: int, bq: int, bk: int) -> float:
    """Visited (query block, key block) pairs over all pairs."""
    return len(visited(t, window, bq, bk)) / ((t // bq) * (t // bk))


def _relative(bq: int, bk: int, keys_down: bool):
    """int32[bq, bk] (or [bk, bq], keys down): a query's place in its block
    less a key's in its own. The same for every pair of blocks: made once
    a step of the grid."""
    shape = (bk, bq) if keys_down else (bq, bk)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 1 if keys_down else 0)
            - jax.lax.broadcasted_iota(jnp.int32, shape,
                                       0 if keys_down else 1))


def _seen(relative, apart, window: int, t: int):
    """Which scores of a pair of blocks whose first query is `apart` after
    its first key the mask lets through: 0 <= query - key < window."""
    seen = relative >= -apart
    return seen if window >= t else seen & (relative < window - apart)


def _as_row(column):
    """float32[1, n] of a float32[n, 1]: through the transpose unit."""
    n = column.shape[0]
    return jnp.broadcast_to(column, (n, _LANES)).T[:1]


def _cut(ref, g, keys):
    """Head `g` of the group's block `ref` [G, rows, d], one part a key of
    `keys`, each as wide as its key: whole where every key is the head's
    own, cut at k's width (a lane tile boundary at the published size)
    where a shared part follows."""
    if len(keys) == 1:
        return (ref[g],)
    own = keys[0].shape[-1]
    return ref[g, :, :own], ref[g, :, own:]


def _scores(rows, columns):
    """float32 sum over the parts of rows[i] columns[i]^T."""
    return reduce(operator.add, (
        jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
        for a, b in zip(rows, columns)))


def _forward(q_ref, k_ref, v_ref, *rest, bq: int, bk: int, window: int,
             scale: float, sink: bool = False):
    # the shared key part, if any, then the sinks [kv, G] (SMEM), if any
    *shared_ref, out_ref, lse_ref = rest
    sink_ref = shared_ref.pop() if sink else None
    at_head = pl.program_id(1) if sink else None  # read outside the loops
    keys = (k_ref, *shared_ref)
    iq = pl.program_id(2)
    first, last = key_blocks(iq, bq, bk, window, jnp.maximum)
    e, t = v_ref.shape[-1], k_ref.shape[0]
    relative = _relative(bq, bk, False)

    def head(g, _):
        q = _cut(q_ref, g, keys)

        def step(j, carry):
            m, l, acc = carry
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            s = _scores(q, [key[at, :] for key in keys])
            s = jnp.where(_seen(relative, iq * bq - j * bk, window, t),
                          s * scale, _HIDDEN)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(p.astype(v_ref.dtype), v_ref[at, :],
                                        preferred_element_type=jnp.float32)
            return m_new, l, acc

        stop = last + 1
        if sink:  # one more column, seen by every row, with no value:
            # the running maximum and sum start from it
            start = (jnp.full((bq, 1), sink_ref[at_head, g],
                              jnp.float32),
                     jnp.ones((bq, 1), jnp.float32))
        else:
            start = (jnp.full((bq, 1), -jnp.inf, jnp.float32),
                     jnp.zeros((bq, 1), jnp.float32))
        m, l, acc = jax.lax.fori_loop(
            first, stop, step, start + (jnp.zeros((bq, e), jnp.float32),))
        out_ref[g] = (acc / l).astype(out_ref.dtype)
        lse_ref[g] = _as_row(m + jnp.log(l))

    # the group's heads one after the other, as a loop: unrolled, a trace
    # of the kernel and its lowering cost the host nine times as much
    jax.lax.fori_loop(0, q_ref.shape[0], head, None)


def _backward(*refs, shared: bool, bq: int, bk: int, window: int,
              scale: float):
    # inputs, results and float32 sums: after each three the shared key
    # part's own (the part, its cotangent, that cotangent's sum), if any
    q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, *r_ref = refs[:6 + shared]
    dq_ref, dk_ref, dv_ref, *dr_ref = refs[6 + shared:9 + 2 * shared]
    dk_sum, dv_sum, *dr_sum = refs[9 + 2 * shared:]
    keys, key_sums = (k_ref, *r_ref), (dk_sum, *dr_sum)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_sum[...] = jnp.zeros_like(dk_sum)
        dv_sum[...] = jnp.zeros_like(dv_sum)

    if shared:  # its sum runs over a window's heads too
        @pl.when((iq == 0) & (pl.program_id(1) == 0))
        def _():
            dr_sum[0][...] = jnp.zeros_like(dr_sum[0])

    first, last = key_blocks(iq, bq, bk, window, jnp.maximum)
    t = k_ref.shape[0]
    relative = _relative(bq, bk, True)

    def head(g, _):
        q = _cut(q_ref, g, keys)
        do = do_ref[g].astype(q[0].dtype)
        lse, di = lse_ref[g], di_ref[g]                       # [1, bq]

        def step(j, dq):
            at = pl.ds(pl.multiple_of(j * bk, bk), bk)
            k, v = [key[at, :] for key in keys], v_ref[at, :]
            s = _scores(k, q)
            s = jnp.where(_seen(relative, iq * bq - j * bk, window, t),
                          s * scale, _HIDDEN)
            p = jnp.exp(s - lse)                              # [bk, bq]
            dv_sum[at, :] += jnp.dot(p.astype(do.dtype), do,
                                     preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, _NT,
                                     preferred_element_type=jnp.float32)
            # ds less its scale, which dq's and dk's sums take once a row
            ds = (p * (dp - di)).astype(do.dtype)
            for total, part in zip(key_sums, q):
                total[at, :] += jnp.dot(ds, part,
                                        preferred_element_type=jnp.float32)
            return tuple(part + jax.lax.dot_general(
                ds, key, _TN, preferred_element_type=jnp.float32)
                for part, key in zip(dq, k))

        dq = jax.lax.fori_loop(
            first, last + 1, step,
            tuple(jnp.zeros(part.shape, jnp.float32) for part in q))
        if len(dq) == 1:
            dq_ref[g] = (dq[0] * scale).astype(dq_ref.dtype)
        else:  # in q's two lane ranges
            own = k_ref.shape[-1]
            dq_ref[g, :, :own] = (dq[0] * scale).astype(dq_ref.dtype)
            dq_ref[g, :, own:] = (dq[1] * scale).astype(dq_ref.dtype)

    jax.lax.fori_loop(0, q_ref.shape[0], head, None)

    @pl.when(iq == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = (dk_sum[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_sum[...].astype(dv_ref.dtype)

    if shared:  # written once a window
        @pl.when((iq == pl.num_programs(2) - 1)
                 & (pl.program_id(1) == pl.num_programs(1) - 1))
        def _():
            dr_ref[0][...] = (dr_sum[0][...] * scale).astype(dr_ref[0].dtype)


def _specs(g: int, t: int, d: int, bq: int):
    """BlockSpecs of (a query head group's block of `d`, a key/value head
    of `d` whole, a group's rows' statistics, [W, kv, G, 1, T]: a head's
    are one lane-major row) on the grid (W, kv, query block)."""
    return (pl.BlockSpec((None, None, g, bq, d),
                         lambda w, h, i: (w, h, 0, i, 0)),
            pl.BlockSpec((None, None, t, d), lambda w, h, i: (w, h, 0, 0)),
            pl.BlockSpec((None, None, g, 1, bq),
                         lambda w, h, i: (w, h, 0, 0, i)))


def _shared_spec(shared):
    """[BlockSpec] of a shared key part [W, 1, T, r], whole: its block
    ignores the head, so it stays in VMEM across a window's heads (and
    its cotangent's block is written back once a window); [] of none."""
    return [pl.BlockSpec((None, None) + part.shape[2:],
                         lambda w, h, i: (w, 0, 0, 0)) for part in shared]


_SEMANTICS = ("parallel", "parallel", "arbitrary")
# with a shared key part the backward's heads run in order: the part's
# cotangent adds up over them in one scratch (the v5e has one core a chip)
_SEMANTICS_SHARED = ("parallel", "arbitrary", "arbitrary")


def _call_forward(interpret, q, k, v, *rest, window, bq, bk, scale, sink):
    # rest: the shared key part, if any, then the sinks, if any
    shared, sinks = (rest[:-1], rest[-1:]) if sink else (rest, ())
    w, kv, g, t, d = q.shape
    e = v.shape[-1]  # the values' width, the scores' apart (== d: the same)
    heads, _, rows = _specs(g, t, d, bq)
    _, whole, _ = _specs(g, t, k.shape[-1], bq)
    heads_e, whole_e, _ = _specs(g, t, e, bq)
    # Mosaic has no 64-bit types: traced with x64 off, as the repo's other
    # kernels are; every operand is 32 bits or narrower already
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_forward, bq=bq, bk=bk, window=window, scale=scale,
                    sink=sink),
            grid=(w, kv, t // bq),
            in_specs=[heads, whole, whole_e] + _shared_spec(shared)
            + [pl.BlockSpec(memory_space=pltpu.SMEM) for _ in sinks],
            out_specs=[heads_e, rows],
            out_shape=[jax.ShapeDtypeStruct(q.shape[:-1] + (e,), jnp.float32),
                       jax.ShapeDtypeStruct((w, kv, g, 1, t), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_SEMANTICS),
            interpret=interpret,
            name="attention_forward",
        )(q, k, v, *shared, *sinks)


def _call_backward(interpret, q, k, v, do, lse, di, *shared, window, bq, bk,
                   scale):
    w, kv, g, t, d = q.shape
    e = v.shape[-1]
    heads, _, rows = _specs(g, t, d, bq)
    _, whole, _ = _specs(g, t, k.shape[-1], bq)
    heads_e, whole_e, _ = _specs(g, t, e, bq)
    part = _shared_spec(shared)
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_backward, shared=bool(shared), bq=bq, bk=bk,
                    window=window, scale=scale),
            grid=(w, kv, t // bq),
            in_specs=[heads, whole, whole_e, heads_e, rows, rows] + part,
            out_specs=[heads, whole, whole_e] + part,
            out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (q, k, v) + shared],
            scratch_shapes=[pltpu.VMEM(a.shape[2:], jnp.float32)
                            for a in (k, v) + shared],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_SEMANTICS_SHARED if shared
                else _SEMANTICS),
            interpret=interpret,
            name="attention_backward",
        )(q, k, v, do, lse, di, *shared)


def _dispatched(call, *operands, **static):
    # the platform being LOWERED FOR picks the branch, so an ahead-of-time
    # compile for a TPU from a CPU host lowers through Mosaic; interpret
    # mode exists for the JAX_PLATFORMS=cpu tests
    call = partial(call, **static)
    return jax.lax.platform_dependent(
        *operands, tpu=partial(call, False), default=partial(call, True))


def _some(*optional):
    """The operands a call was given, in order: a kernel's operand list
    is read off them (None: that operand is not there)."""
    return tuple(a for a in optional if a is not None)


# jitted so that a program traces each shape of them once, however many
# layers and passes call them (a round has two head counts x two masks x
# three window counts, forward, recomputation and backward)
@partial(jax.jit, static_argnames=("window", "bq", "bk", "scale"))
def _run_forward(q, k, v, window, bq, bk, scale, shared=None, sink=None):
    return _dispatched(_call_forward, q, k, v, *_some(shared, sink),
                       window=window, bq=bq, bk=bk, scale=scale,
                       sink=sink is not None)


@partial(jax.jit, static_argnames=("window", "bq", "bk", "scale"))
def _run_backward(q, k, v, out, lse, do, window, bq, bk, scale, shared=None):
    di = jnp.sum(out * do, axis=-1)[..., None, :]
    return _dispatched(_call_backward, q, k, v, do, lse, di, *_some(shared),
                       window=window, bq=bq, bk=bk, scale=scale)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused(q, k, v, window: int, block=None, scale=None, shared=None,
          sink=None):
    """float32[W, kv, G, T, e]: `plain(q, k, v, window, scale, shared,
    sink)` by the kernel, at `block` (query block, key block), or at
    `blocks(G, T, d, q.dtype, e, r)`, which then must take the shape."""
    return _fused_fwd(q, k, v, window, block, scale, shared, sink)[0]


def _static(q, k, v, window, block, scale):
    """(window, query block, key block, scale) of a call on q, k and v."""
    _, _, g, t, d = q.shape
    return ((min(window, t),)
            + tuple(block or blocks(g, t, d, q.dtype, v.shape[-1],
                                    d - k.shape[-1]))
            + (1.0 / math.sqrt(d) if scale is None else float(scale),))


def _fused_fwd(q, k, v, window, block, scale, shared=None, sink=None):
    out, lse = _run_forward(q, k, v, *_static(q, k, v, window, block, scale),
                            shared=shared, sink=sink)
    return out, (q, k, v, shared, sink, out, lse)


def _fused_bwd(window, block, scale, res, do):
    q, k, v, shared, sink, out, lse = res
    dq, dk, dv, *dr = _run_backward(
        q, k, v, out, lse, do, *_static(q, k, v, window, block, scale),
        shared=shared)
    if sink is None:
        return dq, dk, dv, (dr[0] if dr else None), None
    # the sinks' own cotangent needs no kernel: a row's log-sum-exp holds
    # its sink, whose probability exp(sink - lse) weighs no value, so d
    # sink = - sum over the rows of that probability x sum(out * dout). A
    # frozen sink asks for none and the compiler drops this
    mass = jnp.exp(sink[None, :, :, None, None].astype(jnp.float32) - lse)
    d_sink = -jnp.sum(mass[..., 0, :] * jnp.sum(out * do, axis=-1),
                      axis=(0, 3))
    return dq, dk, dv, (dr[0] if dr else None), d_sink.astype(sink.dtype)


fused.defvjp(_fused_fwd, _fused_bwd)


def group_split(g: int, t: int, d: int, dtype, e: int = None,
                shared: int = 0):
    """Into how many sub-groups a key/value head's `g` query heads go so
    that the kernel takes the call: 1 where `blocks` takes the group whole,
    None where it takes no sub-group either. A step of the kernel holds its
    whole sub-group's query block beside the key/value head's whole k, v,
    dk and dv: at windows of 2,048 tokens and scores of 192 those four and
    their sums are 9.4 MB of the 12, and sixteen (or eight) heads' blocks
    do not fit beside them. Of the sub-group sizes that fit, the one whose
    block stands first in `BLOCKS` (fastest first), the larger sub-group
    where two share a block: on the v5e the causal call at 64 heads of 192
    | 128 on 2,048 tokens took 1.91 ms forward and 4.51 with its backward
    a head at a time at 256 x 256, and 3.77 and 8.08 in sub-groups of four
    at 128 x 128; under a window of 128 the two are within 5% of each other
    (eval/eval_attention.py --layers mimo; PERF.md section 6, PR 40)."""
    if blocks(g, t, d, dtype, e, shared) is not None:
        return 1
    found = {g // each: BLOCKS.index(block)
             for each in range(g, 0, -1) if g % each == 0
             for block in [blocks(each, t, d, dtype, e, shared)] if block}
    return min(found, key=lambda s: (found[s], s)) if found else None


def sub_groups(q, k, v, sink, split: int):
    """(q, k, v, sink) of a call whose every key/value head's query heads
    go in `split` sub-groups: each sub-group a key/value head of its own,
    with its own copy of k and v."""
    w, kv, g, t, d = q.shape
    return (q.reshape(w, kv * split, g // split, t, d),
            jnp.repeat(k, split, axis=1), jnp.repeat(v, split, axis=1),
            None if sink is None else sink.reshape(kv * split, g // split))


def attention(q, k, v, window: int, scale=None, shared=None, sink=None):
    """float32[W, kv, G, T, e] = softmax(scale q k^T + mask) v, the mask
    `j <= i and i - j < window`, `scale` 1 / sqrt(d) where None: q [W, kv,
    G, T, d], k [W, kv, T, d], v [W, kv, T, e], the scores' width d and
    the values' e each its own. With `shared` [W, 1, T, r], a key part
    every head has alike (DeepSeek-V2's one rotary key), k is [W, kv, T,
    d - r], a head's key is [k | shared] and no array holds it: the
    scores are q[..., :d - r] k^T + q[..., d - r:] shared^T. With `sink`
    [kv, G], a float a query head (MiMo-V2's learned sink), the softmax's
    denominator holds exp(sink) too, and nothing of it reaches the result.
    The kernel where `blocks` takes the shape, whole or in the sub-groups
    of `group_split` (each then reads its own copy of its key/value head;
    the copies' cotangents add up in the compiler's transpose of the
    copy), the `einsum` form elsewhere. One algorithm, its parameters read
    off the shapes and its operands off what it was given."""
    w, kv, g, t, d = q.shape
    split = group_split(g, t, d, q.dtype, v.shape[-1], d - k.shape[-1])
    if split is None:
        return plain(q, k, v, window, scale, shared, sink)
    if split == 1:
        return fused(q, k, v, window, None, scale, shared, sink)
    q, k, v, sink = sub_groups(q, k, v, sink, split)
    out = fused(q, k, v, window, None, scale, shared, sink)
    return out.reshape(w, kv, g, t, out.shape[-1])
