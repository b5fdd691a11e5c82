"""The four hot device-crypto kernels, vmapped over limb tensors.

  * `msm`              — multi-scalar mult Σ sᵢ·Pᵢ: per-lane MSB-first
                         double-and-add over the 8-bit-limb scalar
                         decomposition PR 6's RLC already produces,
                         then a log₂-depth pointwise tree reduction.
                         Embarrassingly data-parallel: every lane runs
                         the identical 256-step ladder, so the batch
                         vectorizes across the intake width.
  * `fixed_base_mult`  — k·B (and k·H) via a precomputed 2ⁱ·base table:
                         256 conditional adds per lane, no doubles.
  * `grid_validate_sum`— the `ed25519_xy_accum` equivalent: whole-intake
                         all-or-nothing canonicity + on-curve validation
                         of affine commitment grids, plus the pointwise
                         sum of the valid grids (the VSS wave fold).
  * `shamir_recover`   — vectorized Shamir interpolation: the memoized
                         Vandermonde pseudoinverse × aggregated-share
                         matmul on device, rounded back to int64.

Scalars are normalized exactly like `commitments._msm_python` — mod-q
reduction, then top-half scalars become (q−s)·(−P) — so the device MSM
agrees with the CPU backends on EVERY input, torsioned points included
(see _norm_scalar_point). All
jitted programs are cached per power-of-two batch shape — batches pad
with the identity point / zero scalar, which the complete addition
absorbs — so a steady-state round never recompiles.

jax imports are function-local: importing this module (or the package)
from the CPU-only path costs nothing.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from biscotti_tpu.crypto import ed25519 as ed
from biscotti_tpu.crypto.kernels import field as fe
from biscotti_tpu.crypto.kernels import group as gp
from biscotti_tpu.crypto.kernels.instrument import timed

_fn_cache: Dict[tuple, object] = {}
_table_cache: Dict[str, np.ndarray] = {}

# 4p as limb-wise quadrupled P limbs (loose, non-normalized): used for
# host-side point negation −x ≡ 4p − x. 4p rather than 2p because the
# VSS settle negates LOOSE accumulator limbs (< 2¹⁷, which can exceed a
# 2p limb): every 4p limb is ≥ 2¹⁸ − 76, so the result stays
# non-negative at < 2¹⁸ per limb — one bit over the documented loose
# bound, which the fmul analysis absorbs (products < 2³⁶, folded
# < 2⁴⁶, still far inside int64).
_FOURP_LIMBS = 4 * fe.P_LIMBS


def _pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


# lane-count floors: batches pad UP to a power-of-two bucket no smaller
# than these, so a steady-state round compiles each ladder once instead
# of once per intake width (identity-point padding lanes are dead cheap
# next to a 30 s XLA CPU compile; on TPU they vanish into the vector
# width). MSM sees the widest spread of widths (RLC lhs = intake W,
# rhs = C·k), hence the bigger floor.
MSM_MIN_LANES = 32
FIXED_MIN_LANES = 4
GRID_MIN_WAVES = 4


def point_neg_limbs(arr: np.ndarray) -> np.ndarray:
    """Limb-domain point negation (−X, Y, Z, −T) of [..., 4, 16] batches
    with canonical OR loose (< 2¹⁷) coordinate limbs — near-loose
    (< 2¹⁸) output, safe for the ladder's field ops (see _FOURP_LIMBS)."""
    out = np.asarray(arr, dtype=np.int64).copy()
    out[..., 0, :] = _FOURP_LIMBS - out[..., 0, :]
    out[..., 3, :] = _FOURP_LIMBS - out[..., 3, :]
    return out


def _fixed_table(which: str) -> np.ndarray:
    """[256, 4, 16] int64 limb table of 2ⁱ·base for base ∈ {B, H} —
    derived once per process with the python-int oracle (exact)."""
    tab = _table_cache.get(which)
    if tab is None:
        if which == "B":
            pt = ed.BASE
        elif which == "H":
            from biscotti_tpu.crypto.commitments import H_POINT

            pt = H_POINT
        else:
            raise ValueError(f"unknown fixed base {which!r}")
        pts = []
        for _ in range(256):
            pts.append(pt)
            pt = ed.point_double(pt)
        tab = gp.points_to_limbs(pts).astype(np.int64)
        _table_cache[which] = tab
    return tab


# ------------------------------------------------------------- compiled


class CompileError(RuntimeError):
    """The backend's compiler refused a kernel. Not a device fault: the
    same program will be refused every time, so no caller fails over to
    the CPU on it — it propagates, carrying the compiler's message."""


def _get(key, builder, *args):
    """The executable for `key`, compiled for `args`' shapes on first
    use. Compilation is explicit (ahead of the call, not inside it) so a
    compiler refusal surfaces as CompileError, apart from whatever the
    device does at run time."""
    fn = _fn_cache.get(key)
    if fn is None:
        import jax

        try:
            fn = builder().lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            raise CompileError(
                f"device-crypto kernel {key} does not compile on "
                f"{jax.default_backend()}: {e}") from e
        _fn_cache[key] = fn
    return fn


def _build_msm(n: int):
    import jax
    import jax.numpy as jnp

    ident = jnp.asarray(np.broadcast_to(gp.IDENTITY_LIMBS,
                                        (n, 4, fe.LIMBS)).copy())

    def run(bits, pts):
        def body(i, acc):
            acc = gp.point_double(acc)
            return gp.select(bits[:, i] > 0, gp.point_add(acc, pts), acc)

        acc = jax.lax.fori_loop(0, 256, body, ident)
        return gp.tree_sum(acc)

    return jax.jit(run)


def _build_fixed(n: int):
    import jax
    import jax.numpy as jnp

    ident = jnp.asarray(np.broadcast_to(gp.IDENTITY_LIMBS,
                                        (n, 4, fe.LIMBS)).copy())

    def run(bits, table):
        # bits [n, steps] LSB-first against table[i] = 2ⁱ·base (tables
        # may be concatenated: B‖H walks both in one loop)
        steps = bits.shape[1]

        def body(i, acc):
            t = jnp.broadcast_to(table[i], (n, 4, fe.LIMBS))
            return gp.select(bits[:, i] > 0, gp.point_add(acc, t), acc)

        return jax.lax.fori_loop(0, steps, body, ident)

    return jax.jit(run)


def _build_grid(w: int, n: int):
    import jax
    import jax.numpy as jnp

    def run(xy):  # [w, n, 2, 16] int64
        x = xy[..., 0, :]
        y = xy[..., 1, :]
        ok = fe.lt_p(x) & fe.lt_p(y) & gp.on_curve(x, y)  # [w, n]
        grid_ok = jnp.all(ok, axis=1)  # [w]
        one = jnp.broadcast_to(
            jnp.asarray(fe.ONE_LIMBS), (w, n, fe.LIMBS)).astype(x.dtype)
        pts = jnp.stack([x, y, one, fe.fmul(x, y)], axis=-2)
        ident = jnp.broadcast_to(jnp.asarray(gp.IDENTITY_LIMBS),
                                 (w, n, 4, fe.LIMBS)).astype(x.dtype)
        pts = jnp.where(grid_ok[:, None, None, None], pts, ident)
        summed = gp.tree_sum(pts)  # [n, 4, 16]
        return grid_ok, summed

    return jax.jit(run)


def _build_ext_add():
    import jax

    return jax.jit(lambda a, b: gp.point_add(a, b))


def _build_recover():
    import jax
    import jax.numpy as jnp

    def run(pinv, agg):
        sol = pinv @ agg.astype(jnp.float64)  # [k, C]
        return jnp.round(sol).astype(jnp.int64)

    return jax.jit(run)


# ----------------------------------------------------------- public API


def _norm_scalar_point(scalars, pts_limbs) -> Tuple[np.ndarray, np.ndarray]:
    """Signed/unreduced python-int scalars + [n,4,16] limb points →
    (MSB-first bit matrix, possibly-negated limb points), mirroring
    `commitments._msm_python`'s pair normalization EXACTLY: reduce mod
    q (python semantics cover negatives), then replace top-half scalars
    by (q−s)·(−P). The mirror matters beyond bit-shortness: s·P and
    (q−s)·(−P) differ by q·P, which is NOT the identity for points
    carrying a small-order (torsion) component — commitment-grid cells
    are validated on-curve but NOT subgroup-checked, so without the
    identical fold an adversarial torsioned cell would make the device
    and CPU settles disagree on the same input (consensus split — the
    exact hazard _msm_python's own normalization exists to close).
    Zero scalars ride along (their adds never fire)."""
    mags: List[int] = []
    pts = np.asarray(pts_limbs, dtype=np.int64)
    neg_idx = []
    for i, s in enumerate(scalars):
        s = int(s) % fe.Q
        if s > fe.Q // 2:
            s = fe.Q - s
            neg_idx.append(i)
        mags.append(s)
    if neg_idx:
        pts = pts.copy()
        pts[neg_idx] = point_neg_limbs(pts[neg_idx])
    bits = fe.scalars_to_bits(mags, msb_first=True)
    return bits, pts


def msm(scalars: Sequence[int], points) -> ed.Point:
    """Σ sᵢ·Pᵢ on device. `points` is a sequence of extended python-int
    points or an [n, 4, 16] limb array (e.g. `CommitKey.device_buf`).
    Returns an extended python-int point — projectively equal (identical
    group element) to the CPU oracle's result on every input."""
    n = len(scalars)
    if n == 0:
        return ed.IDENTITY
    with timed("msm"):
        if isinstance(points, np.ndarray):
            pts = np.asarray(points[:n], dtype=np.int64)
        else:
            pts = gp.points_to_limbs(points).astype(np.int64)
        bits, pts = _norm_scalar_point(scalars, pts)
        m = _pow2(n, MSM_MIN_LANES)
        if m != n:
            bits = np.concatenate(
                [bits, np.zeros((m - n, 256), bits.dtype)])
            pts = np.concatenate(
                [pts, np.broadcast_to(gp.IDENTITY_LIMBS,
                                      (m - n, 4, fe.LIMBS))])
        bits = bits.astype(np.int32)
        fn = _get(("msm", m), lambda: _build_msm(m), bits, pts)
        out = np.asarray(fn(bits, pts))
    return gp.limbs_to_point(out)


def fixed_base_mult(scalars: Sequence[int], which: str = "B") -> List[ed.Point]:
    """[kᵢ·base] for base ∈ {B, H}: 256 conditional table adds per lane,
    vmapped across the batch. Scalars reduce mod q (fixed-base callers
    are group-order scalars by construction)."""
    n = len(scalars)
    if n == 0:
        return []
    with timed("fixed_base"):
        red = [int(s) % fe.Q for s in scalars]
        bits = fe.scalars_to_bits(red, msb_first=False)
        m = _pow2(n, FIXED_MIN_LANES)
        if m != n:
            bits = np.concatenate(
                [bits, np.zeros((m - n, 256), bits.dtype)])
        bits = bits.astype(np.int32)
        table = _fixed_table(which)
        fn = _get(("fixed", m), lambda: _build_fixed(m), bits, table)
        out = np.asarray(fn(bits, table))
    return [gp.limbs_to_point(out[i]) for i in range(n)]


def pedersen_commit_point(a: int, b: int) -> ed.Point:
    """a·B + b·H in ONE device ladder (the concatenated-table walk) —
    the lhs comb of the batched VSS / commitment equations."""
    with timed("fixed_base"):
        bits = np.concatenate([
            fe.scalars_to_bits([int(a) % fe.Q], msb_first=False),
            fe.scalars_to_bits([int(b) % fe.Q], msb_first=False),
        ], axis=1).astype(np.int32)  # [1, 512]
        table = np.concatenate([_fixed_table("B"), _fixed_table("H")])
        fn = _get(("pedersen",), lambda: _build_fixed(1), bits, table)
        out = np.asarray(fn(bits, table))
    return gp.limbs_to_point(out[0])


def grid_validate_sum(grids: Sequence) -> Tuple[np.ndarray,
                                                Optional[np.ndarray]]:
    """Whole-wave commitment-grid validation + pointwise sum — the
    device `ed25519_xy_accum`. `grids`: W buffers of n packed 64-byte
    affine (x, y) pairs (bytes or uint8 arrays of any shape totalling
    n·64 bytes). Returns (ok mask [W] bool, summed [n, 4, 16] int64 over
    the VALID grids — None when none are valid).

    Verdict parity with the CPU loaders is exact: a grid is ok iff every
    cell has canonical (< p) coordinates AND lies on the curve (subgroup
    NOT checked — callers fold the cofactor 8 into verification scalars,
    exactly like the native plane)."""
    w = len(grids)
    if w == 0:
        return np.zeros(0, dtype=bool), None
    bufs = [bytes(g) if isinstance(g, (bytes, bytearray))
            else np.ascontiguousarray(g).tobytes() for g in grids]
    n = len(bufs[0]) // 64
    with timed("grid_validate"):
        xy = np.stack([gp.xy_bytes_to_limbs(b, n)
                       for b in bufs]).astype(np.int64)  # [w, n, 2, 16]
        wp = _pow2(w, GRID_MIN_WAVES)
        if wp != w:
            pad = np.zeros((wp - w, n, 2, fe.LIMBS), dtype=np.int64)
            pad[..., 1, 0] = 1  # affine identity (0, 1): valid, sums away
            xy = np.concatenate([xy, pad])
        fn = _get(("grid", wp, n), lambda: _build_grid(wp, n), xy)
        grid_ok, summed = fn(xy)
        mask = np.asarray(grid_ok)[:w]
        if not mask.any():
            return mask, None
        summed_np = np.asarray(summed)
    return mask, summed_np


def ext_add(acc: np.ndarray, other: np.ndarray) -> np.ndarray:
    """Pointwise acc[i] += other[i] over two [n, 4, 16] limb batches —
    the accumulator fold of the incremental VSS intake."""
    with timed("ext_add"):
        acc = np.asarray(acc, np.int64)
        other = np.asarray(other, np.int64)
        fn = _get(("ext_add", acc.shape[0]), _build_ext_add, acc, other)
        return np.asarray(fn(acc, other))


def shamir_recover(pinv: np.ndarray, agg: np.ndarray) -> np.ndarray:
    """[k, S] Vandermonde pseudoinverse × [S, C] aggregated shares on
    device, rounded → [C, k] int64 chunk coefficients (the
    `ss.recover_coeffs` tail)."""
    with timed("shamir_recover"):
        pinv = np.asarray(pinv, np.float64)
        agg = np.asarray(agg, np.int64)
        fn = _get(("recover", pinv.shape, agg.shape), _build_recover,
                  pinv, agg)
        sol = np.asarray(fn(pinv, agg))
    return np.ascontiguousarray(sol.T)


def prewarm(grid_points: int = 0) -> None:
    """Compile the ladder kernels at the bucket shapes a cluster of this
    dimensionality will hit (`grid_points` = C·k, the commitment-grid
    width), so XLA compile time is paid ONCE at peer startup instead of
    inside a round deadline. No-op when the plane is disarmed. This IS
    the start-up compile: a kernel the backend refuses raises
    CompileError here, before the first round, not mid-round."""
    from biscotti_tpu.crypto import kernels
    from biscotti_tpu.crypto.kernels import instrument

    if not kernels.active():
        return
    # suppressed: warm-up wall-clock must not pollute the round-work
    # instrumentation (seconds accumulators, histogram, spans)
    with instrument.suppressed():
        fixed_base_mult([1])
        pedersen_commit_point(1, 1)
        n = max(1, int(grid_points))
        msm([1] * n, [ed.BASE] * n)
        if grid_points:
            ident = np.zeros((n, 64), np.uint8)
            ident[:, 32] = 1  # affine identity (0, 1): on-curve
            grid_validate_sum([ident])


__all__ = [
    "msm", "fixed_base_mult", "pedersen_commit_point",
    "grid_validate_sum", "ext_add", "shamir_recover", "point_neg_limbs",
]
