"""The gated delta rule of a linear-attention layer (Gated Delta Networks,
arXiv:2412.06464) in its chunked form, in plain `jax.numpy` under
`jax.grad`: beside ops/ssm.py the second operation of `ops/` that keeps a
state along the sequence, and the first whose update is not a decayed
outer product: every token also takes out of the state what the state
already answers for its key.

A value head h of a window holds a state S in R^{D x E} (D the key's
width, E the value's) that starts from ZERO at the window's first token:

    S   = exp(g_t) S_{t-1}                       g_t <= 0, a head and token
    d_t = beta_t (v_t - S^T k_t)                 beta_t in (0, 1)
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
    stable whatever the keys (the product form of (I + A)^-1 is not, its
    powers of A grow where a chunk's keys align), and all small products,
    at the highest precision. The compiler's own `triangular_solve` took
    2.5 ms a window and layer on the v5e, 1.3 s of a 4.9 s round (PERF.md
    section 6, PR 38). No inverse of the whole system is formed.
  windows never meet: W is a batch axis of every product, and a chunk never
    spans two windows (T is a whole number of chunks, or one chunk).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from biscotti_tpu.ops.ssm import chunks  # noqa: F401  (one rule, re-exported)

SUB = 16  # rows of a diagonal block of a chunk's system, inverted row by row


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


def _grouped(q, v, g, beta):
    """(v [W, T, G, R, E], g, beta [W, T, G, R]): the value heads by the
    key head they read."""
    w, t, groups, _ = q.shape
    heads, e = v.shape[2:]
    if heads % groups:
        raise ValueError(f"{heads} value heads on {groups} key heads")
    r = heads // groups
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
