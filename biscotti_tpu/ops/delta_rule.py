"""The gated delta rule of a linear-attention layer (Gated Delta Networks,
arXiv:2412.06464) in its chunked form: beside ops/ssm.py the second
operation of `ops/` that keeps a state along the sequence, and the first
whose update is not a decayed outer product: every token also takes out of
the state what the state already answers for its key. `rule` is what a
model calls; it runs ONE algorithm in one of two forms, read off the
shapes: a fused Pallas TPU kernel pair under one `jax.custom_vjp`
(`fused`; PR 39) where the widths are whole lane tiles, or `laid` makes
them so with zero columns (heads of 96 | 192; PR 48), and plain
`jax.numpy` under `jax.grad` (`chunked`) elsewhere: the tiny presets'
heads of 8 and chunks of 4, the float64 tests, and the kernel's oracle.

A value head h of a window holds a state S in R^{D x E} (D the key's
width, E the value's) that starts from ZERO at the window's first token:

    S   = exp(g_t) S_{t-1}                       g_t <= 0, a head and token
    d_t = beta_t (v_t - S^T k_t)                 beta_t in (0, 2)
    S_t = S + k_t d_t^T                          (so S_t = exp(g_t) S_{t-1}
    o_t = S_t^T q_t                               (I - beta_t k_t k_t^T) + beta_t k_t v_t^T)

q and k are a KEY head's (G of them, each serving R = H / G value heads: h
reads key head h // R) and arrive normalised (`l2norm`, q times D^-0.5).
`sequential` is that recurrence as written, a token at a time (`lax.scan`
over T). `chunked` computes the same o a CHUNK of L tokens at a time (the
WY / UT transform of the paper's section 3.3), so that all but T / L steps
are matrix products. With gamma_i = sum_{j <= i} g_j inside the chunk and
S_0 the state the chunk starts from,

    d_i = beta_i (v_i - exp(gamma_i) S_0^T k_i
                  - sum_{j < i} exp(gamma_i - gamma_j) (k_i . k_j) d_j)

is a unit-lower-triangular system (I + A) D = rhs, A_ij = beta_i
exp(gamma_i - gamma_j) (k_i . k_j) below the diagonal, whose right side is
linear in S_0. ONE solve a chunk, of [beta v | beta exp(gamma) k], gives U
and W with D = U - W S_0, for every chunk at once; then

    carried   D_c = U_c - W_c S;  S <- exp(gamma_L) S + (exp(gamma_L -
              gamma) k)^T D_c       (float32, T / L steps of `lax.scan`)
    o_i = exp(gamma_i) S_0^T q_i + sum_{j <= i} exp(gamma_i - gamma_j)
          (q_i . k_j) d_j            (every chunk at once, after the scan)

ops/ssm.py's scan cannot express it: its chunk has no solve and its decay
no k k^T term.

  precision: as ops/ssm.py's. The log-decays, their cumulative sums, the
    system's matrix and its solve and the carried state in float32; the
    operands of every product (k k^T, q k^T, W S, the masked scores times
    D, k^T D, q S) in q's own type (bfloat16 at the published size) with
    float32 accumulation. A decay is exp of a difference that is <= 0
    where the mask lets it through; where it does not, the difference is
    set to -inf BEFORE the exp.
  the solve is forward substitution in blocks of `SUB` rows (`_solve`):
    stable whatever the keys, at every beta in (0, 2) (a model that lets
    the eigenvalue along k go negative doubles its sigmoid,
    arXiv:2411.12537, and hands `rule` the beta it means). Entry (i, j)
    of (I + A)^-1 is -beta_i k_i^T P k_j times a decay, P the product of
    the steps between, I - beta k k^T, each of norm <= 1 while |k| <= 1
    and beta <= 2: no entry passes 2, and substitution forms those
    entries, block by block, and nothing larger (the product form's
    powers of A grow where a chunk's keys align, as 2^n at beta = 2). All
    small products, at the highest precision; no whole inverse is formed.
  windows never meet: W is a batch axis of every product, and a chunk never
    spans two windows (T is a whole number of chunks, or one chunk).

What is `jax.numpy`'s (`chunked`, `_solve`): every chunk's decays, system,
right side, solution and deltas are ARRAYS in HBM, float32 [W, N, G, R, 64,
64] and [..., 64, 256] (300 MB a forward call at the published size), six
head-major transposes, and the carried part a `lax.scan` of small launches:
1.44 | 3.64 ms a window and layer, 3.6% of the rule's roofline (PR 38).

What is the kernel's (`fused`: `_forward`, `_backward`; the same
mathematics at the same precision, statement for statement):
  grid (window, block of `KEY_HEADS` key heads with their R value heads
    each, chunk), the chunk axis sequential and innermost. The carried
    state S [D, E] float32 a value head lives in VMEM scratch across a
    head block's chunks and is zeroed at a window's first chunk; windows
    are a grid axis, so W windows cost W times one.
  a step makes, all in VMEM: the running sums of g (a masked sum: no
    product rounds them), the decays, k k^T and q k^T once a key head,
    the strictly lower system, its solve for [U | W], delta = U - W S, the
    state's update and o. Nothing of [64, 64] or [64, 256] goes to HBM.
  operands as the conv writes them: q, k [W, T, G D] and v, o [W, T, H E]
    token-major, a head a lane-tile column range of a (64, heads x width)
    block. No transpose but g's and beta's ([W, T, H]), laid a column a head.
  the solve is the same blocked forward substitution: the four diagonal
    blocks of `SUB` rows inverted by substitution on the identity (15
    rank-1 steps on the vector unit), then a block row at a time on the
    matrix unit at the highest precision. A step's value heads (four or
    more: `heads_a_step`) solve IN STEP with each other (`_inverses`,
    `_lower`, `_upper` take lists): a head at a time the chip waits out
    seven dependent small products (0.69 | 1.97 ms a call; 0.39 | 1.11).
  backward: a kernel too, the chunks in reverse, dS carried in VMEM, the
    chunk's system, solve and delta made again on chip from q, k, v, g,
    beta and the chunk's ENTRY STATE, which the forward under `jax.grad`
    writes out (float32 [W, N, H, D, E]: 33.5 MB a window and layer, live
    only inside a `jax.checkpoint`ed layer's backward; 82 us of HBM
    traffic a call where walking the chunks a second time costs a forward,
    390 us). The solve's cotangent is the transposed substitution, dR =
    (I + A)^-T dX, dA = -tril(dR X^T, -1); dg is the reverse running sum.
    Cotangents enter their products in the operands' type, as
    ops/attention.py's do (and as the chip's default precision rounds
    them in the `jax.numpy` form).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one lowering choice (Mosaic or interpret mode) and one spelling of the
# transposed products for the repo's kernels
from biscotti_tpu.ops.attention import _LANES, _NT, _TN, _dispatched
from biscotti_tpu.ops.ssm import chunks  # noqa: F401  (one rule, re-exported)

SUB = 16  # rows of a diagonal block of a chunk's system, inverted row by row
# key heads a step of the kernel's grid holds (each with its R value
# heads): two independent chains of small products for the scheduler to
# interleave, and half the steps (eval/eval_delta_rule.py)
KEY_HEADS = 2
_NN = (((1,), (0,)), ((), ()))  # a @ b


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, in x's type."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _solve(system, rhs):
    """X of (I + system) X = rhs by forward substitution: system [..., L, L]
    strictly lower triangular, rhs [..., L, N], in rhs's type (float32),
    every product at the highest precision. The diagonal blocks of `SUB`
    rows are inverted a row at a time, all at once (row i of (I + A)^-1 is
    e_i - A[i] (I + A)^-1, and A[i] reads only the rows already made);
    then a block row at a time X_i = T_ii (rhs_i - sum_{j < i} A_ij X_j)."""
    size = system.shape[-1]
    sub = math.gcd(size, SUB)
    cuts = [slice(at, at + sub) for at in range(0, size, sub)]

    def dot(left, right):
        return jnp.matmul(left, right, precision=jax.lax.Precision.HIGHEST)

    diagonal = jnp.stack([system[..., cut, cut] for cut in cuts], axis=-3)
    inverse = jnp.broadcast_to(jnp.eye(sub, dtype=rhs.dtype), diagonal.shape)
    for row in range(1, sub):
        made = dot(diagonal[..., row:row + 1, :], inverse)   # [..., 1, sub]
        inverse = inverse - (np.arange(sub) == row)[:, None] * made
    solved = []
    for at, cut in enumerate(cuts):
        right = rhs[..., cut, :]
        if at:
            right = right - dot(system[..., cut, :at * sub],
                                jnp.concatenate(solved, axis=-2))
        solved.append(dot(inverse[..., at, :, :], right))
    return jnp.concatenate(solved, axis=-2)


def _each(groups: int, heads: int) -> int:
    """R: the value heads a key head serves."""
    if heads % groups:
        raise ValueError(f"{heads} value heads on {groups} key heads")
    return heads // groups


def _grouped(q, v, g, beta):
    """(v [W, T, G, R, E], g, beta [W, T, G, R]): the value heads by the
    key head they read."""
    w, t, groups, _ = q.shape
    heads, e = v.shape[2:]
    r = _each(groups, heads)
    return (v.reshape(w, t, groups, r, e), g.reshape(w, t, groups, r),
            beta.reshape(w, t, groups, r))


def sequential(q, k, v, g, beta):
    """The recurrence a token at a time, in g's type (float32): q, k [W, T,
    G, D] normalised, v [W, T, H, E], g (<= 0), beta [W, T, H];
    float32[W, T, H, E]. What `chunked` is held to (tests,
    eval/eval_delta_rule.py)."""
    w, t, groups, d = q.shape
    v, g, beta = _grouped(q, v, g, beta)
    q, k, v = (a.astype(g.dtype) for a in (q, k, v))

    def step(state, item):
        q_t, k_t, v_t, g_t, beta_t = item      # [W, G, D], [W, G, R(, E)]
        state = jnp.exp(g_t)[..., None, None] * state
        delta = beta_t[..., None] * (
            v_t - jnp.einsum("wgrde,wgd->wgre", state, k_t))
        state = state + k_t[:, :, None, :, None] * delta[..., None, :]
        return state, jnp.einsum("wgrde,wgd->wgre", state, q_t)

    _, out = jax.lax.scan(
        step, jnp.zeros((w, groups) + v.shape[3:4] + (d, v.shape[-1]),
                        g.dtype),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1).reshape(w, t, -1, v.shape[-1])


def chunked(q, k, v, g, beta, chunk: int):
    """float32[W, T, H, E] = `sequential(q, k, v, g, beta)` in chunks of
    `chunk` tokens (module doc): q, k [W, T, G, D] and v [W, T, H, E] in
    one type, the products' operands'; g (<= 0) and beta float32[W, T,
    H]."""
    w, t, groups, d = q.shape
    dtype, f32 = q.dtype, g.dtype  # float32; the tests' float64 runs through
    n = chunks(t, chunk)
    size = t // n
    v, g, beta = _grouped(q, v, g, beta)
    r, e = v.shape[3:]

    def dot(spec, left, right):
        return jnp.einsum(spec, left.astype(dtype), right.astype(dtype),
                          preferred_element_type=f32)

    # a chunk's tokens minor-most but for the width: [W, N, G, (R,) L, ...]
    qc = q.reshape(w, n, size, groups, d).transpose(0, 1, 3, 2, 4)
    kc = k.reshape(w, n, size, groups, d).transpose(0, 1, 3, 2, 4)
    vc = v.reshape(w, n, size, groups, r, e).transpose(0, 1, 3, 4, 2, 5)
    gc = g.reshape(w, n, size, groups, r).transpose(0, 1, 3, 4, 2)
    bc = beta.reshape(w, n, size, groups, r).transpose(0, 1, 3, 4, 2)

    cum = jnp.cumsum(gc, axis=-1)                          # gamma, <= 0
    grown = jnp.exp(cum)
    seen = np.tril(np.ones((size, size), bool))            # j <= i
    decay = jnp.exp(jnp.where(
        seen, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    kk = dot("wngid,wngjd->wngij", kc, kc)[:, :, :, None]  # [W,N,G,1,L,L]
    qk = dot("wngid,wngjd->wngij", qc, kc)[:, :, :, None]
    keys = kc.astype(f32)[:, :, :, None]                   # [W,N,G,1,L,D]

    # (I + A) [U | W] = [beta v | beta exp(gamma) k]: one solve a chunk
    system = jnp.where(np.tril(seen, -1), bc[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate([bc[..., None] * vc.astype(f32),
                           (bc * grown)[..., None] * keys], axis=-1)
    solved = _solve(system, rhs)                           # [W,N,G,R,L,E+D]
    u, wy = solved[..., :e], solved[..., e:]

    # the states carried from chunk to chunk, and each chunk's deltas
    to_end = jnp.exp(cum[..., -1:] - cum)[..., None] * keys

    def carry(state, item):
        u_c, wy_c, to_end_c, whole = item
        delta = u_c - dot("wgrld,wgrde->wgrle", wy_c, state)
        after = whole[..., None, None] * state \
            + dot("wgrld,wgrle->wgrde", to_end_c, delta)
        return after, (state, delta)

    _, (before, delta) = jax.lax.scan(
        carry, jnp.zeros((w, groups, r, d, e), f32),
        tuple(a.swapaxes(0, 1) for a in (u, wy, to_end, grown[..., -1])))
    before, delta = before.swapaxes(0, 1), delta.swapaxes(0, 1)
    out = grown[..., None] * dot("wngld,wngrde->wngrle", qc, before) \
        + dot("wngrij,wngrje->wngrie", decay * qk, delta)
    return out.transpose(0, 1, 4, 2, 3, 5).reshape(w, t, groups * r, e)


# ------------------------------------------------- the kernel (module doc)


def _mm(left, right, dims=_NN):
    """A product of operands in their own type, accumulated in float32."""
    return jax.lax.dot_general(left, right, dims,
                               preferred_element_type=jnp.float32)


def _mm32(left, right, dims=_NN):
    """A float32 product at the highest precision (the solve's)."""
    return jax.lax.dot_general(left, right, dims,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


class _Grid:
    """A chunk's index grids [L, L], made once a step: `seen` j <= i,
    `below` j < i, `same` j == i, and the two moves between a column [L, 1]
    and a row [1, L] that they allow without a transpose (a sum over a
    masked broadcast: exact, every other term is 0)."""

    def __init__(self, size: int):
        i = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
        self.seen, self.below, self.same = j <= i, j < i, j == i
        self.ahead = j >= i
        self.last = i[:, :1] == size - 1                      # [L, 1]
        sub = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
        self.unit = sub.astype(jnp.float32)                   # I of a block

    def row(self, column):
        return jnp.sum(jnp.where(self.same, column, 0.0), axis=0,
                       keepdims=True)

    def column(self, row):
        return jnp.sum(jnp.where(self.same, row, 0.0), axis=1,
                       keepdims=True)


def _inverses(systems, unit):
    """[[(I + A_bb)^-1 a diagonal block of `SUB` rows] a system] of
    `systems` (each [L, L], strictly lower): forward substitution on the
    identity, a column of A at a time (row j of the inverse is final after
    step j - 1; every later row then loses A[i, j] times it). On the vector
    unit: 15 steps, every block of every system in step with the others
    (they are independent: a step's latency is hidden behind the others')."""
    size = systems[0].shape[0]
    blocks = [system[at:at + SUB, at:at + SUB] for system in systems
              for at in range(0, size, SUB)]
    made = [unit] * len(blocks)
    for j in range(SUB - 1):
        made = [inverse - block[:, j:j + 1] * inverse[j:j + 1, :]
                for block, inverse in zip(blocks, made)]
    each = size // SUB
    return [made[at:at + each] for at in range(0, len(made), each)]


def _lower(systems, inverses, sides):
    """[X of (I + system) X = rhs] of systems in step, a block row at a
    time, as `_solve`: X_b = T_bb (rhs_b - sum_{j < b} A_bj X_j)."""
    solved = [[] for _ in systems]
    for b in range(len(inverses[0])):
        at = b * SUB
        rights = [rhs[at:at + SUB, :] for rhs in sides]
        if b:
            rights = [right - _mm32(system[at:at + SUB, :at],
                                    jnp.concatenate(done, axis=0))
                      for right, system, done in zip(rights, systems, solved)]
        for done, inverse, right in zip(solved, inverses, rights):
            done.append(_mm32(inverse[b], right))
    return [jnp.concatenate(done, axis=0) for done in solved]


def _upper(systems, inverses, sides):
    """[Y of (I + system)^T Y = rhs], the solve's cotangent: the
    transposed substitution, from the last block row up, Y_b = T_bb^T
    (rhs_b - sum_{j > b} A_jb^T Y_j)."""
    solved = [[] for _ in systems]
    for b in reversed(range(len(inverses[0]))):
        at = b * SUB
        rights = [rhs[at:at + SUB, :] for rhs in sides]
        if solved[0]:
            rights = [right - _mm32(system[at + SUB:, at:at + SUB],
                                    jnp.concatenate(done, axis=0), _TN)
                      for right, system, done in zip(rights, systems, solved)]
        for done, inverse, right in zip(solved, inverses, rights):
            done.insert(0, _mm32(inverse[b], right, _TN))
    return [jnp.concatenate(done, axis=0) for done in solved]


def _chunks(grid, heads):
    """A step's value heads' chunks, each from the state it starts with
    (module doc), their solves in step with each other. A head is (q, k
    [L, D] and v [L, E] in the operands' type, kk = k k^T and qk = q k^T
    float32[L, L] (its key head's: made once for the R value heads), g,
    beta float32[L, 1], state float32[D, E]). A dict a head of everything
    the forward writes and the backward reads again."""
    made = []
    for q, k, kk, qk, v, g, beta, state in heads:
        cum = jnp.sum(jnp.where(grid.seen, grid.row(g), 0.0), axis=1,
                      keepdims=True)                          # gamma, <= 0
        decay = jnp.exp(jnp.where(grid.seen, cum - grid.row(cum), -jnp.inf))
        grown = jnp.exp(cum)
        whole = jnp.sum(jnp.where(grid.last, cum, 0.0), axis=0,
                        keepdims=True)
        keys, values = k.astype(jnp.float32), v.astype(jnp.float32)
        made.append(dict(
            cum=cum, decay=decay, grown=grown, last=whole, keys=keys,
            values=values, whole=jnp.exp(whole), low=state.astype(q.dtype),
            system=jnp.where(grid.below, beta * decay * kk, 0.0),
            rhs=jnp.concatenate([beta * values, (beta * grown) * keys],
                                axis=1)))
    systems = [c["system"] for c in made]
    inverses = _inverses(systems, grid.unit)
    solved = _lower(systems, inverses, [c.pop("rhs") for c in made])
    for c, head, inverse, x in zip(made, heads, inverses, solved):
        q, _, _, qk, v = head[:5]
        e = v.shape[1]
        wy = x[:, e:].astype(q.dtype)                         # [U | W]
        to_end = jnp.exp(c.pop("last") - c["cum"])            # [L, 1]
        c.update(inverses=inverse, solved=x, wy=wy, to_end=to_end,
                 delta=(x[:, :e] - _mm(wy, c["low"])).astype(q.dtype),
                 ended=(to_end * c["keys"]).astype(q.dtype),
                 scores=(c["decay"] * qk).astype(q.dtype),
                 read=_mm(q, c["low"]))
    return made


def _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, states, each: int, d: int,
           e: int):
    """The heads of a step as `_chunks` takes them, and each key head's
    lane range of q's block."""
    heads, ranges = [], []
    for key_head in range(q_ref.shape[1] // d):
        at = slice(key_head * d, (key_head + 1) * d)
        q, k = q_ref[:, at], k_ref[:, at]
        kk, qk = _mm(k, k, _NT), _mm(q, k, _NT)
        ranges.append(at)
        for h in range(key_head * each, (key_head + 1) * each):
            heads.append((q, k, kk, qk, v_ref[:, h * e:(h + 1) * e],
                          g_ref[:, h:h + 1], beta_ref[:, h:h + 1],
                          states[h]))
    return heads, ranges


def _forward(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, *rest,
             each: int):
    *states_ref, state = rest  # the entry states, where they are kept
    d, e = state.shape[1:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    heads, _ = _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, state, each, d, e)
    for h, (c, head) in enumerate(zip(_chunks(_Grid(q_ref.shape[0]), heads),
                                      heads)):
        if states_ref:
            states_ref[0][h] = head[-1]
        out_ref[:, h * e:(h + 1) * e] = c["grown"] * c["read"] \
            + _mm(c["scores"], c["delta"])
        state[h] = c["whole"] * head[-1] + _mm(c["ended"], c["delta"], _TN)


def _backward(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref, do_ref,
              dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
              each: int):
    d, e = dstate.shape[1:]
    grid = _Grid(q_ref.shape[0])
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)  # the window's LAST chunk: walked back
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    heads, ranges = _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, states_ref,
                           each, d, e)
    made = _chunks(grid, heads)
    # o = grown (q S) + scores delta;  S' = whole S + ended^T delta;
    # delta = U - W S: up to the cotangent of [U | W]
    sides = []
    for h, (c, (q, *_)) in enumerate(zip(made, heads)):
        do, ds = do_ref[:, h * e:(h + 1) * e], dstate[h]
        lows = (do.astype(dtype), ds.astype(dtype))
        ddelta = _mm(c["scores"], lows[0], _TN) + _mm(c["ended"], lows[1])
        low = ddelta.astype(dtype)
        reads = (c["grown"] * do).astype(dtype)
        c.update(ds=ds, lows=lows, reads=reads,
                 dgrown=jnp.sum(do * c["read"], axis=1, keepdims=True),
                 dscores=jnp.where(grid.seen, _mm(lows[0], c["delta"], _NT),
                                   0.0))
        dstate[h] = c["whole"] * ds + _mm(q, reads, _TN) \
            - _mm(c["wy"], low, _TN)
        sides.append(jnp.concatenate([ddelta, -_mm(low, c["low"], _NT)],
                                     axis=1))
    # [U | W] = (I + A)^-1 rhs: the transposed substitutions, in step
    solved = _upper([c["system"] for c in made],
                    [c["inverses"] for c in made], sides)
    sums = {}
    for h, (c, head, dsolved) in enumerate(zip(made, heads, solved)):
        q, k, kk, qk, _, _, beta, entry = head
        grown, decay, ds = c["grown"], c["decay"], c["ds"]
        dsystem = -jnp.where(grid.below, _mm32(dsolved, c["solved"], _NT),
                             0.0)
        du, dw = dsolved[:, :e], dsolved[:, e:]
        dv_ref[:, h * e:(h + 1) * e] = (beta * du).astype(dv_ref.dtype)
        both = jnp.sum(dw * c["keys"], axis=1, keepdims=True)
        pair = dsystem * decay                                # [L, L]
        dbeta_ref[:, h:h + 1] = (
            jnp.sum(du * c["values"], axis=1, keepdims=True)
            + grown * both + jnp.sum(pair * kk, axis=1, keepdims=True))
        # the state's update: ended = to_end k, whole = exp(gamma_L)
        dended = _mm(c["delta"], c["lows"][1], _NT)           # [L, D]
        past = jnp.sum(dended * c["to_end"] * c["keys"], axis=1,
                       keepdims=True)
        dwhole = jnp.sum(past, axis=0, keepdims=True) + c["whole"] \
            * jnp.sum(jnp.sum(ds * entry, axis=1, keepdims=True), axis=0,
                      keepdims=True)
        # gamma: through exp(gamma), the decays and the last row
        moved = (dsystem * beta * kk + c["dscores"] * qk) * decay
        dcum = (c["dgrown"] + beta * both) * grown - past \
            + jnp.sum(moved, axis=1, keepdims=True) \
            - grid.column(jnp.sum(moved, axis=0, keepdims=True)) \
            + jnp.where(grid.last, dwhole, 0.0)
        # g: the reverse running sum
        dg_ref[:, h:h + 1] = jnp.sum(
            jnp.where(grid.ahead, grid.row(dcum), 0.0), axis=1,
            keepdims=True)
        # the key head's own: summed over its value heads in float32
        mine = (_mm(c["reads"], c["low"], _NT),
                (beta * grown) * dw + c["to_end"] * dended,
                beta * pair, c["dscores"] * decay)
        so_far = sums.get(h // each)
        sums[h // each] = mine if so_far is None else tuple(
            a + b for a, b in zip(so_far, mine))
    for key_head, (dq, dk, dkk, dqk) in sums.items():
        at = ranges[key_head]
        q, k = heads[key_head * each][:2]
        dkk, dqk = dkk.astype(dtype), dqk.astype(dtype)
        dq_ref[:, at] = (dq + _mm(dqk, k)).astype(dq_ref.dtype)
        dk_ref[:, at] = (dk + _mm(dkk, k) + _mm(dkk, k, _TN)
                         + _mm(dqk, q, _TN)).astype(dk_ref.dtype)


def _specs(n: int, size: int, d: int, e: int, held: int, each: int,
           back: bool):
    """BlockSpecs on the grid (window, block of `held` key heads, chunk) of
    (q's and k's columns, v's and o's, g's and beta's [W, blocks, N, L,
    heads], the entry states' [W, N, H, D, E]); `back`: the chunks from
    the last to the first."""
    heads = held * each

    def chunk(c):
        return n - 1 - c if back else c

    return (pl.BlockSpec((None, size, held * d),
                         lambda w, h, c: (w, chunk(c), h)),
            pl.BlockSpec((None, size, heads * e),
                         lambda w, h, c: (w, chunk(c), h)),
            pl.BlockSpec((None, None, None, size, heads),
                         lambda w, h, c: (w, h, chunk(c), 0, 0)),
            pl.BlockSpec((None, None, heads, d, e),
                         lambda w, h, c: (w, chunk(c), h, 0, 0)))


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _call_forward(interpret, q, k, v, g, beta, *, d, e, held, keep):
    w = q.shape[0]
    blocks, n, size, heads = g.shape[1:]
    keys, values, columns, states = _specs(
        n, size, d, e, held, heads // held, False)
    out = [jax.ShapeDtypeStruct(v.shape, jnp.float32)]
    if keep:
        out.append(jax.ShapeDtypeStruct((w, n, blocks * heads, d, e),
                                        jnp.float32))
    # Mosaic has no 64-bit types: traced with x64 off, as the repo's other
    # kernels are
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_forward, each=heads // held),
            grid=(w, blocks, n),
            in_specs=[keys, keys, values, columns, columns],
            out_specs=[values, states][:len(out)],
            out_shape=out,
            scratch_shapes=[pltpu.VMEM((heads, d, e), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_SEMANTICS),
            interpret=interpret,
            name="delta_rule_forward",
        )(q, k, v, g, beta)


def _call_backward(interpret, q, k, v, g, beta, states, do, *, d, e, held):
    w = q.shape[0]
    blocks, n, size, heads = g.shape[1:]
    keys, values, columns, kept = _specs(
        n, size, d, e, held, heads // held, True)
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_backward, each=heads // held),
            grid=(w, blocks, n),
            in_specs=[keys, keys, values, columns, columns, kept, values],
            out_specs=[keys, keys, values, columns, columns],
            out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype)
                       for a in (q, k, v, g, beta)],
            scratch_shapes=[pltpu.VMEM((heads, d, e), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=_SEMANTICS),
            interpret=interpret,
            name="delta_rule_backward",
        )(q, k, v, g, beta, states, do)


def _columns(a, n: int, heads: int):
    """float32[W, blocks, N, L, heads] of a [W, T, H]: a step's value
    heads' g (or beta) a column each."""
    w, t, h = a.shape
    return a.astype(jnp.float32).reshape(
        w, n, t // n, h // heads, heads).transpose(0, 3, 1, 2, 4)


def _rows(a):
    """[W, T, H] of a `_columns`."""
    w, blocks, n, size, heads = a.shape
    return a.transpose(0, 2, 3, 1, 4).reshape(w, n * size, blocks * heads)


def key_heads_a_step(groups: int) -> int:
    """Key heads a step of the kernel's grid holds."""
    return math.gcd(groups, KEY_HEADS)


def _operands(q, k, v, g, beta, chunk):
    """The kernel's operands and its static widths: q, k [W, T, G D] and v
    [W, T, H E] token-major as they come, g and beta a column a head."""
    w, t, groups, d = q.shape
    heads, e = v.shape[2:]
    n = chunks(t, chunk)
    held = heads_a_step(groups, heads)
    across = held * _each(groups, heads)
    return ((q.reshape(w, t, -1), k.reshape(w, t, -1), v.reshape(w, t, -1),
             _columns(g, n, across), _columns(beta, n, across)),
            dict(d=d, e=e, held=held))


# jitted so that a program traces each shape of them once, however many
# layers and passes call them
@partial(jax.jit, static_argnames=("chunk", "keep"))
def _run_forward(q, k, v, g, beta, chunk, keep):
    operands, static = _operands(q, k, v, g, beta, chunk)
    out, *states = _dispatched(_call_forward, *operands, keep=keep, **static)
    return (out.reshape(v.shape), *states)


@partial(jax.jit, static_argnames=("chunk",))
def _run_backward(q, k, v, g, beta, states, do, chunk):
    operands, static = _operands(q, k, v, g, beta, chunk)
    dq, dk, dv, dg, dbeta = _dispatched(
        _call_backward, *operands, states,
        do.astype(jnp.float32).reshape(do.shape[:2] + (-1,)), **static)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            _rows(dg).astype(g.dtype), _rows(dbeta).astype(beta.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def fused(q, k, v, g, beta, chunk: int):
    """float32[W, T, H, E] = `chunked(q, k, v, g, beta, chunk)` by the
    kernel: shapes that `fits` takes."""
    return _run_forward(q, k, v, g, beta, chunk, False)[0]


def _fused_fwd(q, k, v, g, beta, chunk):
    out, states = _run_forward(q, k, v, g, beta, chunk, True)
    return out, (q, k, v, g, beta, states)


def _fused_bwd(chunk, res, do):
    return _run_backward(*res, do, chunk)


fused.defvjp(_fused_fwd, _fused_bwd)


def fits(t: int, d: int, e: int, chunk: int, dtype) -> bool:
    """Whether the kernel takes windows of `t` in chunks of `chunk` at
    widths d | e: both whole lane tiles, the chunk (or the one shorter
    window) whole blocks of `SUB` rows, the operands bfloat16 or
    float32."""
    return (d % _LANES == 0 and e % _LANES == 0
            and (t // chunks(t, chunk)) % SUB == 0
            and jnp.dtype(dtype) in (jnp.bfloat16, jnp.float32))


def plan(groups: int, t: int, d: int, e: int, chunk: int, dtype,
         heads: int = 0) -> dict:
    """Which side of `rule`'s dispatch a model is built with, from the
    shapes alone, `heads` its value heads (two a key head where a model
    states none). The keys are `layout`'s, below `rule`: what PR 48
    brought stands there, so that no line a published round traces moved
    (a kernel's compiled payload holds its call stack, line by line)."""
    if not heads:
        heads = 2 * groups
    return layout(groups, heads, t, d, e, chunk, dtype)


def rule(q, k, v, g, beta, chunk: int):
    """float32[W, T, H, E] = `sequential(q, k, v, g, beta)` in chunks of
    `chunk`: the kernel where `fits` takes the shapes, the `jax.numpy`
    form `chunked` elsewhere. One algorithm, its form read off the
    shapes."""
    t, d, e = q.shape[1], q.shape[-1], v.shape[-1]
    if q.dtype == v.dtype == k.dtype and fits(t, d, e, chunk, q.dtype):
        return fused(q, k, v, g, beta, chunk)
    return laid(q, k, v, g, beta, chunk)


# ---------------------------------------- heads that are no whole lane tiles
# (PR 48. All of it stands BELOW `rule`: a Mosaic payload embeds its call
# stack, and a line added above would re-key every program a published
# round compiles from this file; PERF.md section 6, PR 46. ROADMAP Queue C
# has the merge.)

IN_STEP = 4  # value heads whose solves run in step with each other (PR 39)


def heads_a_step(groups: int, heads: int) -> int:
    """Key heads a step of the kernel's grid holds, each with its R value
    heads: the fewest that divide G and bring `IN_STEP` value heads
    together (2 of 16 at two value heads a key head, 5 of 30 at one); all
    of G where no divisor does."""
    each = _each(groups, heads)
    return next(held for held in range(1, groups + 1)
                if groups % held == 0
                and (held * each >= IN_STEP or held == groups))


def _tiles(width: int) -> int:
    """`width` rounded up to whole lane tiles."""
    return -(-width // _LANES) * _LANES


def padded_share(d: int, e: int) -> float:
    """Of the kernel's state products (D x E a token and value head), the
    share that multiplies the zero columns `laid` puts in: 0 at whole lane
    tiles, 0.4375 at 96 | 192."""
    return 1.0 - d * e / (_tiles(d) * _tiles(e))


def _lays(t: int, d: int, e: int, chunk: int, dtype) -> bool:
    """Whether `laid` takes widths d | e to the kernel: the tiles above
    them fit it and under half of its state products would multiply
    zeros (a head of 64 or less on a tile of 128 stays `chunked`'s)."""
    return 0.0 < padded_share(d, e) < 0.5 \
        and fits(t, _tiles(d), _tiles(e), chunk, dtype)


def laid(q, k, v, g, beta, chunk: int):
    """`rule` where the widths are no whole lane tiles: the kernel on
    heads laid in whole tiles with ZERO columns where `_lays` says so,
    `chunked` elsewhere. Exact: a zero key column adds nothing to k . k,
    q . k or S^T k and its row of the state stays zero; a zero value column
    gives a zero column of d, of the state and of o, which is dropped.
    The cotangents of the zero columns are dropped by the pad's own
    transpose."""
    t, d, e = q.shape[1], q.shape[-1], v.shape[-1]
    if not (q.dtype == v.dtype == k.dtype
            and _lays(t, d, e, chunk, q.dtype)):
        return chunked(q, k, v, g, beta, chunk)

    def wide(a, width):
        return jnp.pad(a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))

    return fused(wide(q, _tiles(d)), wide(k, _tiles(d)),
                 wide(v, _tiles(e)), g, beta, chunk)[..., :e]


def layout(groups: int, heads: int, t: int, d: int, e: int, chunk: int,
           dtype) -> dict:
    """What `plan` states: `kernel` 1 the fused kernel (0: the `jax.numpy`
    form), `key_heads_a_step` and `value_heads_a_step` what a step of its
    grid holds, `padded_share` as above (0 off the kernel), `states_saved`
    1: the forward under `jax.grad` keeps each chunk's entry state for the
    backward (it does not walk the chunks twice)."""
    kernel = fits(t, d, e, chunk, dtype) or _lays(t, d, e, chunk, dtype)
    held = heads_a_step(groups, heads) if kernel else 0
    return {"kernel": int(kernel), "key_heads_a_step": held,
            "value_heads_a_step": held * heads // groups,
            "padded_share": padded_share(d, e) if kernel else 0.0,
            "states_saved": int(kernel)}
