"""The state-space recurrence of a Mamba-2 layer in its chunked (SSD,
"state-space duality") form, in plain `jax.numpy` under `jax.grad`: the
only operation of `ops/` that keeps a state along the sequence.

A head h of a window holds a state S in R^{P x N} (P the head's width, N
the state's) that starts from ZERO at the window's first token:

    S_t = exp(dt_t a_h) S_{t-1} + dt_t x_t (x) B_t        a_h < 0, dt_t > 0
    y_t = S_t C_t + d_h x_t

with B_t, C_t in R^N shared by every head (one group). `sequential` is
that recurrence as written, a token at a time (`lax.scan` over T): T
steps, each a pass over [W, H, P, N]. `scan` computes the same y a CHUNK of
L tokens at a time (arXiv:2405.21060 section 6), so that all but T / L
steps are matrix products:

    cum_i  = sum_{j <= i} dt_j a_h, inside the chunk       (float32)
    inside   y_i += sum_{j <= i} exp(cum_i - cum_j) (C_i . B_j) dt_j x_j
             the masked product (L o C B^T)(dt x), L_ij = exp(cum_i - cum_j)
    a chunk's own state   sum_j exp(cum_end - cum_j) dt_j x_j (x) B_j
    carried  S_k = exp(cum_end) S_{k-1} + the chunk's own  (float32, T / L
             steps of `lax.scan`)
    from before the chunk   y_i += exp(cum_i) S_{k-1} C_i

  precision: the decays, their cumulative sums and the carried state in
    float32; the operands of every product (C B^T, the masked scores, dt x,
    B, C, the state a chunk starts from) in x's own type (bfloat16 at the
    published size) with float32 accumulation, as every product of
    models/lm.py. A decay is exp of a difference that is <= 0 where the
    mask lets it through; where it does not, the difference is set to -inf
    BEFORE the exp (exp of the positive difference would overflow, and
    its zero cotangent times inf is NaN in the backward).
  layout: the scores [W, K, H, L, L] keep the chunk's keys minor (256 lanes
    at the published chunk; H = 64 minor would fill half a lane tile).
  windows never meet: W is a batch axis of every product, and a chunk never
    spans two windows (T is a whole number of chunks, or one chunk).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def chunks(length: int, chunk: int) -> int:
    """Chunks a window of `length` tokens is walked in: whole chunks of
    `chunk`, or the window as one where it is shorter."""
    size = min(chunk, length)
    if length % size:
        raise ValueError(f"a window of {length} tokens is no whole number "
                         f"of chunks of {chunk}")
    return length // size


def sequential(x, dt, a, b, c, d):
    """The recurrence a token at a time, in dt's type (float32): x [W, T,
    H, P], dt float32[W, T, H], a, d float32[H], b, c [W, T, N]; float32[W,
    T, H, P]. What `scan` is held to (tests, eval/eval_ssm.py)."""
    x, b, c = (v.astype(dt.dtype) for v in (x, b, c))

    def step(state, item):
        x_t, dt_t, b_t, c_t = item                 # [W, H, P], [W, H], [W, N]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("whpn,wn->whp", state, c_t)

    w, _, h, p = x.shape
    _, y = jax.lax.scan(
        step, jnp.zeros((w, h, p, b.shape[-1]), dt.dtype),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1) + d[:, None] * x


def scan(x, dt, a, b, c, d, chunk: int):
    """float32[W, T, H, P] = `sequential(x, dt, a, b, c, d)` in chunks of
    `chunk` tokens (module doc): x [W, T, H, P], b, c [W, T, N] in one type,
    the products' operands'; dt float32[W, T, H] (after its softplus), a
    (negative), d float32[H]."""
    w, t, h, p = x.shape
    n, dtype = b.shape[-1], x.dtype
    k = chunks(t, chunk)
    size = t // k
    f32 = dt.dtype  # float32; the tests' float64 runs through unrounded

    def dot(spec, left, right):
        return jnp.einsum(spec, left, right, preferred_element_type=f32)

    xf = x.astype(f32)
    xdt = (xf * dt[..., None]).reshape(w, k, size, h, p)    # the step's input
    bs, cs = b.reshape(w, k, size, n), c.reshape(w, k, size, n)
    cum = jnp.cumsum((dt * a).reshape(w, k, size, h), axis=2)   # <= 0
    cum_h = cum.transpose(0, 1, 3, 2)                           # [W, K, H, L]

    # inside a chunk: the masked scores (L o C B^T), keys minor
    seen = np.tril(np.ones((size, size), bool))
    decay = jnp.exp(jnp.where(
        seen, cum_h[..., :, None] - cum_h[..., None, :], -jnp.inf))
    scores = dot("wkin,wkjn->wkij", cs, bs)[:, :, None] * decay
    y = dot("wkhij,wkjhp->wkihp", scores.astype(dtype), xdt.astype(dtype))

    # a chunk's own state, and the states carried from chunk to chunk
    last = cum[:, :, -1]                                        # [W, K, H]
    to_end = jnp.exp(last[:, :, None] - cum)[..., None]         # [W,K,L,H,1]
    own = dot("wklhp,wkln->wkhpn", (xdt * to_end).astype(dtype), bs)

    def carry(state, item):
        whole, mine = item
        return whole[..., None, None] * state + mine, state

    _, before = jax.lax.scan(
        carry, jnp.zeros((w, h, p, n), f32),
        (jnp.exp(last).swapaxes(0, 1), own.swapaxes(0, 1)))
    before = before.swapaxes(0, 1)                              # [W,K,H,P,N]
    y = y + dot("wkln,wkhpn->wklhp", cs, before.astype(dtype)) \
        * jnp.exp(cum)[..., None]
    return y.reshape(w, t, h, p) + d[:, None] * xf
