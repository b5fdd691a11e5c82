"""Fused Krum scoring as a Pallas TPU kernel for large committees.

The XLA path in ops/krum.py (one [n,d]x[d,n] matmul + lax.top_k with
k ~ n/2) is ideal up to a few hundred peers, but at large n it
materializes the full n x n distance matrix in HBM and pays a per-row
sort for the "sum of the k smallest" reduction (top_k at k ~ n/2 lowers
to a full variadic sort). This kernel fuses the whole score pipeline
(SURVEY.md §2.3 row 18 calls Krum the flagship device kernel; the
reference computes it in numpy on the verifier's CPU,
ML/Pytorch/client_obj.py:114-143):

  grid (row-tile i, feature-tile kd), kd innermost:
    1. accumulate G[i-tile, :] += X[i-tile, kd] . X[:, kd]^T on the MXU
       into a VMEM scratch — the n x n Gram/distance matrix exists only
       as one (TILE_M, n) stripe at a time, never in HBM;
    2. at the last kd step, form D = |xi|^2 + |xj|^2 - 2G, mask the
       diagonal and column padding to +inf, and run an EXACT per-row
       selection of the k-th smallest distance by bisection on the
       float bit pattern (non-negative IEEE floats compare like their
       int bits, so 31 VPU passes pin the exact value — no sort, no
       approximation);
    3. score_i = sum(D < t_i) + (k - count_lt) * t_i  — exactly the
       reference's sum of the (n - f - 2) nearest distances, with ties
       at the threshold handled the way a sorted prefix would.

Scores match ops/krum.krum_scores to float-sum reassociation (tested
bit-tight at 1e-4 rtol, including duplicate-update ties). The dispatcher
krum_scores_auto keeps the XLA path for small n and switches to this
kernel when the committee is large enough for the fusion to pay.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 128
# f32 sign bit is never set for distances (>= 0, +inf mask included),
# so bisection over bits 30..0 pins the exact k-th smallest value
_SELECT_BITS = 31


def _select_kth_and_sum(dist: jax.Array, k: int) -> jax.Array:
    """Per-row sum of the k smallest entries of `dist` (TILE_M, n_pad),
    exact selection via integer bisection on the float bit pattern.
    Returns (TILE_M, 1) float32."""
    bits = jax.lax.bitcast_convert_type(dist, jnp.int32)

    def body(t, ans):
        cand = ans | (1 << (_SELECT_BITS - 1 - t))
        cnt_lt = jnp.sum((bits < cand).astype(jnp.int32), axis=1,
                         keepdims=True)
        # count(x < cand) >= k  =>  k-th smallest < cand: bit stays 0
        return jnp.where(cnt_lt >= k, ans, cand)

    ans = jax.lax.fori_loop(
        0, _SELECT_BITS, body,
        jnp.zeros((dist.shape[0], 1), jnp.int32))
    kth = jax.lax.bitcast_convert_type(ans, jnp.float32)
    below = bits < ans
    cnt_lt = jnp.sum(below.astype(jnp.int32), axis=1, keepdims=True)
    ssum = jnp.sum(jnp.where(below, dist, 0.0), axis=1, keepdims=True)
    # ties at the threshold: a sorted prefix would take (k - cnt_lt)
    # copies of the k-th value
    return ssum + (k - cnt_lt).astype(jnp.float32) * kth


def _krum_kernel(x_row_ref, x_all_ref, sq_row_ref, sq_col_ref, out_ref,
                 gram, *, n: int, k: int, kd_steps: int):
    i = pl.program_id(0)
    kd = pl.program_id(1)

    @pl.when(kd == 0)
    def _():
        gram[:] = jnp.zeros_like(gram)

    gram[:] += jax.lax.dot_general(
        x_row_ref[:], x_all_ref[:],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kd == kd_steps - 1)
    def _():
        n_pad = gram.shape[1]
        d = sq_row_ref[:] + sq_col_ref[:] - 2.0 * gram[:]
        d = jnp.maximum(d, 0.0)  # clamp fp cancellation noise
        cols = jax.lax.broadcasted_iota(jnp.int32, (TILE_M, n_pad), 1)
        rows = jax.lax.broadcasted_iota(jnp.int32, (TILE_M, n_pad), 0)
        rows = rows + i * TILE_M
        # self-distance (the reference's sorted[0] drop) + column padding
        d = jnp.where((cols == rows) | (cols >= n), jnp.inf, d)
        out_ref[:] = _select_kth_and_sum(d, k)


@functools.partial(jax.jit, static_argnames=("num_adversaries",))
def krum_scores_pallas(deltas: jax.Array, num_adversaries: int) -> jax.Array:
    """Krum scores (ops/krum.krum_scores semantics) via the fused kernel.

    score_i = sum of the (n - f - 2) smallest off-diagonal squared
    distances in row i (ref: client_obj.py:127-143).
    """
    n, d = deltas.shape
    groupsize = n - num_adversaries
    k = max(groupsize - 2, 0)
    if k == 0:
        return jnp.zeros((n,), jnp.float32)

    n_pad = -(-n // TILE_M) * TILE_M
    # feature tile: bounded VMEM for the (n_pad, d_t) operand stripe
    d_t = 256 if n_pad <= 4096 else 128
    d_pad = -(-d // d_t) * d_t
    with jax.named_scope("krum_prepare"):  # parallel/sim.py STAGES
        x = deltas.astype(jnp.float32)
        x = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
        sq = jnp.sum(x * x, axis=-1)  # zero padding leaves norms exact
    kd_steps = d_pad // d_t

    kernel = functools.partial(_krum_kernel, n=n, k=k, kd_steps=kd_steps)

    def call(interpret, x, sq_col, sq_row):
        # Mosaic has no 64-bit types: under jax_enable_x64 the kernel's
        # weak Python ints (loop indices, index maps, shifts) would trace
        # as int64 and fail to lower, so the call is traced with x64 off.
        # Every operand is already float32, so no value changes.
        with jax.enable_x64(False):
            return pl.pallas_call(
                kernel,
                grid=(n_pad // TILE_M, kd_steps),
                in_specs=[
                    pl.BlockSpec((TILE_M, d_t), lambda i, kd: (i, kd),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((n_pad, d_t), lambda i, kd: (0, kd),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((TILE_M, 1), lambda i, kd: (i, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, n_pad), lambda i, kd: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((TILE_M, 1), lambda i, kd: (i, 0),
                                       memory_space=pltpu.VMEM),
                out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
                scratch_shapes=[pltpu.VMEM((TILE_M, n_pad), jnp.float32)],
                interpret=interpret,
            )(x, x, sq_col, sq_row)

    # the platform being LOWERED FOR picks the branch (not the process's
    # default backend), so an ahead-of-time compile for a TPU topology
    # from a CPU host lowers through Mosaic like the chip does; interpret
    # mode exists only for the JAX_PLATFORMS=cpu tests
    with jax.named_scope("krum_scores"):
        scores = jax.lax.platform_dependent(
            x, sq[:, None], sq[None, :],
            tpu=functools.partial(call, False),
            default=functools.partial(call, True))
        return scores[:n, 0]


# committees below this stay on the XLA matmul+top_k path (one fused HLO,
# no grid/padding overhead). The window's speed-up is unmeasured on the
# current machine (eval/eval_krum_kernel.py regenerates it from a device
# trace); what the v5e run of PR 21 established is correctness across it.
PALLAS_MIN_N = 512
# above this the kernel's VMEM working set (double-buffered (n_pad, d_t)
# operand stripe + (TILE_M, n_pad) gram scratch) outgrows VMEM — fall back
# to XLA. The ceiling itself was re-checked on the v5e with libtpu 0.0.34
# (chip_smoke.py, PR 21): n = 4096 at d = 164,266 compiles and runs.
PALLAS_MAX_N = 4096


def krum_scores_auto(deltas: jax.Array, num_adversaries: int) -> jax.Array:
    """Dispatch Krum scoring: XLA path for small committees (and for
    n beyond the kernel's VMEM ceiling), the fused Pallas kernel for
    large ones on TPU. "On TPU" is the platform the enclosing program is
    being lowered for, so an ahead-of-time compile for the chip makes the
    chip's choice wherever it runs.

    Deployment constraint (ADVICE r3): inside the [PALLAS_MIN_N,
    PALLAS_MAX_N] window the accept set is backend-dependent — Pallas and
    XLA scores agree to float-sum reassociation (2.3e-7 relative seen on
    the v5e, both 4.1e-6 off a float64 oracle), so tie-boundary accept
    sets can differ between a TPU verifier and a CPU verifier. All verifiers of one
    cluster must therefore share a backend (see docs/RUNTIME.md,
    "Verifier backend homogeneity"). The live protocol's committees
    (3-70 verifiers) sit below PALLAS_MIN_N, where every backend takes
    the same XLA path, so the constraint binds only for sampled-committee
    sizes >= 512."""
    from biscotti_tpu.ops.krum import krum_scores

    if PALLAS_MIN_N <= deltas.shape[0] <= PALLAS_MAX_N:
        return jax.lax.platform_dependent(
            deltas,
            tpu=lambda x: krum_scores_pallas(x, num_adversaries),
            default=lambda x: krum_scores(x, num_adversaries))
    return krum_scores(deltas, num_adversaries)
