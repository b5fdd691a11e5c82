"""ctypes bridge to the C++ crypto library (native/libbiscotti_native.so).

Loaded lazily; `available()` is False (and the pure-Python paths run) until
`make -C native` has produced the shared object. Negative scalars are
handled here by negating the point — the C side sees small non-negative
scalars, which keeps Pippenger window counts minimal for quantized updates.
"""

from __future__ import annotations

import ctypes
import os
from typing import List, Optional, Sequence, Tuple

from biscotti_tpu.crypto import ed25519 as ed

_LIB_PATHS = [
    os.path.join(os.path.dirname(__file__), "..", "..", "native",
                 "libbiscotti_native.so"),
]

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
# why the native plane is unavailable ("" while loaded / not yet probed):
# surfaced ONCE on stderr at load time — the pure-Python fallback keeps
# every caller correct (parity-tested), but silently eating a ~30x miner
# crypto slowdown deep inside a round was the old failure mode
_load_error = ""


def load_error() -> str:
    """Human-readable reason the native library is unavailable, or ""
    when it loaded (or was never needed). Probes the loader."""
    _load()
    return _load_error


def _degrade(reason: str) -> None:
    """Record and announce the pure-Python degradation, once."""
    global _load_error
    _load_error = reason
    import sys

    print(f"[crypto/_native] native EC backend unavailable: {reason} — "
          f"falling back to the pure-Python path (correct, parity-tested, "
          f"~30x slower miner crypto). Build the `libbiscotti_native.so` "
          f"target with `make -C native` to restore it.", file=sys.stderr)


def _build() -> str:
    """Run `make -C native`; returns "" or why the build failed. The .so
    is never committed and never trusted from another machine: the
    Makefile keys it to (sources, this host's CPU flags), so make is a
    no-op when the binary was built here from these sources and a
    rebuild otherwise. Disable with BISCOTTI_NO_NATIVE_BUILD=1."""
    if os.environ.get("BISCOTTI_NO_NATIVE_BUILD"):
        return ""
    import subprocess

    native_dir = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", "native"))
    try:
        subprocess.run(["make", "-C", native_dir], check=True,
                       capture_output=True, text=True, timeout=300)
    except subprocess.CalledProcessError as e:
        return f"`make -C native` failed: {e.stderr.strip()[-400:]}"
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"`make -C native` did not run: {e}"
    return ""


def _selfcheck(lib: ctypes.CDLL) -> bool:
    """Cross-check the loaded binary against the pure-Python backend on a
    small random instance; a stale or tampered .so is refused, silently
    falling back to Python."""
    import secrets

    scalars = [int.from_bytes(secrets.token_bytes(16), "little") + 1
               for _ in range(4)]
    points = [ed.scalar_mult(i + 2, ed.BASE) for i in range(4)]
    expect = ed.IDENTITY
    for s, p in zip(scalars, points):
        expect = ed.point_add(expect, ed.scalar_mult(s % ed.Q, p))
    sbuf = b"".join((s % ed.Q).to_bytes(32, "little") for s in scalars)
    pbuf = b"".join(_point_bytes(p) for p in points)
    out = ctypes.create_string_buffer(64)
    if lib.ed25519_msm(sbuf, pbuf, 4, out) != 0:
        return False
    return ed.point_equal(point_from_xy64(out.raw), expect)


def _try_load(full: str) -> Tuple[Optional[ctypes.CDLL], str]:
    """(loaded library, "") or (None, reason). AttributeError means the
    binary's exported symbols predate the sources — an ABI-stale .so —
    which gets its own actionable message."""
    try:
        lib = ctypes.CDLL(full)
        lib.ed25519_msm.restype = ctypes.c_int
        lib.ed25519_msm.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p,
        ]
        lib.ed25519_batch_commit.restype = ctypes.c_int
        lib.ed25519_batch_commit.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_batch_commit_signed.restype = ctypes.c_int
        lib.ed25519_batch_commit_signed.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_char_p,
        ]
        lib.ed25519_load_xy_batch.restype = ctypes.c_int
        lib.ed25519_load_xy_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_msm_signed.restype = ctypes.c_int
        lib.ed25519_msm_signed.argtypes = [
            # points arg is c_void_p: accepts bytes AND mutable buffers
            # (the VSS intake accumulator passes its bytearray zero-copy)
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_vss_rlc_scalars.restype = ctypes.c_int
        lib.ed25519_vss_rlc_scalars.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        lib.ed25519_vss_st_accum.restype = ctypes.c_int
        lib.ed25519_vss_st_accum.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_char_p,
            ctypes.c_char_p,
        ]
        lib.ed25519_vss_blind_rows.restype = ctypes.c_int
        lib.ed25519_vss_blind_rows.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_decompress_batch.restype = ctypes.c_int
        lib.ed25519_decompress_batch.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_load_xy_sum.restype = ctypes.c_int
        lib.ed25519_load_xy_sum.argtypes = [
            ctypes.c_char_p, ctypes.c_size_t, ctypes.c_size_t,
            ctypes.c_char_p,
        ]
        lib.ed25519_load_xy_sum_ptrs.restype = ctypes.c_int
        lib.ed25519_load_xy_sum_ptrs.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
            ctypes.c_size_t, ctypes.c_char_p,
        ]
        lib.ed25519_xy_accum.restype = ctypes.c_int
        lib.ed25519_xy_accum.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ]
        lib.ed25519_ext_accum.restype = ctypes.c_int
        lib.ed25519_ext_accum.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ]
        if not _selfcheck(lib):
            return None, (f"{full} failed the cross-backend self-check "
                          "(stale or tampered binary)")
        return lib, ""
    except AttributeError as e:
        return None, (f"{full} is ABI-stale — exported symbols predate "
                      f"the sources ({e})")
    except OSError as e:
        return None, f"{full} failed to load ({e})"


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    _load_attempted = True
    # always let make run: it is a no-op when the .so was built on this
    # host from these sources, and rebuilds a stale or foreign binary
    build_error = _build()
    reason = ""
    found = False
    for path in _LIB_PATHS:
        full = os.path.abspath(path)
        if not os.path.exists(full):
            continue
        found = True
        lib, reason = _try_load(full)
        if lib is None:
            # one retry in case the first build raced/failed
            build_error = _build()
            lib, reason = _try_load(full)
        if lib is not None:
            _lib = lib
            break
    if _lib is None:
        _degrade(build_error or (reason if found else
                 "native/libbiscotti_native.so not found (never built, "
                 "or BISCOTTI_NO_NATIVE_BUILD=1 suppressed the build)"))
    return _lib


def available() -> bool:
    return _load() is not None


def _fe_bytes(v: int) -> bytes:
    return (v % ed.P).to_bytes(32, "little")


def _buf_addr(obj) -> Tuple[int, int, object]:
    """(address, byte length, keepalive) for a bytes-like object or a
    C-contiguous numpy array — zero-copy either way. The keepalive must
    stay referenced for the duration of the native call: the address
    points into the object's own storage."""
    if isinstance(obj, bytes):
        addr = ctypes.cast(ctypes.c_char_p(obj), ctypes.c_void_p).value
        return addr or 0, len(obj), obj
    if isinstance(obj, bytearray):
        raw = (ctypes.c_char * len(obj)).from_buffer(obj)
        return ctypes.addressof(raw), len(obj), (obj, raw)
    # numpy (or anything with the array interface); a non-contiguous view
    # degrades to one copy rather than corrupt reads
    import numpy as _np

    arr = _np.ascontiguousarray(obj)
    return int(arr.ctypes.data), arr.nbytes, arr


def point_from_xy64(buf: bytes) -> ed.Point:
    """Unpack one 64-byte little-endian affine (x, y) pair — the native
    library's output wire shape — into an extended-coordinate point."""
    x = int.from_bytes(buf[:32], "little")
    y = int.from_bytes(buf[32:64], "little")
    return (x, y, 1, (x * y) % ed.P)


def _point_bytes(p: ed.Point) -> bytes:
    x, y, z, t = p
    return _fe_bytes(x) + _fe_bytes(y) + _fe_bytes(z) + _fe_bytes(t)


def msm(scalars: Sequence[int], points: Sequence[ed.Point]) -> ed.Point:
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(scalars) != len(points):
        raise ValueError("scalar/point length mismatch")
    sbuf = bytearray()
    pbuf = bytearray()
    n = 0
    for s, p in zip(scalars, points):
        s = s % ed.Q
        if s == 0:
            continue
        # keep scalars short: a value in the top half of Z_q is a small
        # negative — use |s| with the negated point instead
        if s > ed.Q // 2:
            s = ed.Q - s
            p = ed.point_neg(p)
        sbuf += s.to_bytes(32, "little")
        pbuf += _point_bytes(p)
        n += 1
    if n == 0:
        return ed.IDENTITY
    out = ctypes.create_string_buffer(64)
    rc = lib.ed25519_msm(bytes(sbuf), bytes(pbuf), n, out)
    if rc != 0:
        raise RuntimeError(f"native msm failed: {rc}")
    return point_from_xy64(out.raw)


def load_xy_batch(xy: bytes, n: int) -> Optional[bytes]:
    """n×64B affine (x,y) pairs → n×128B extended buffer, with canonicity
    and on-curve validation (NOT subgroup — fold cofactor 8 into scalars).
    None if any point is invalid."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(xy) != 64 * n:
        raise ValueError("xy buffer length mismatch")
    out = ctypes.create_string_buffer(128 * n)
    rc = lib.ed25519_load_xy_batch(xy, n, out)
    if rc != 0:
        return None
    return out.raw


def vss_rlc_scalars(xs: Sequence[int], gammas_buf: bytes, c_chunks: int,
                    k: int) -> Tuple[bytes, bytes]:
    """Fused RLC → MSM-ready buffers: returns (scalars 32B·C·k magnitudes
    with cofactor 8 folded in, signs C·k bytes) consumable directly by
    msm_signed_raw. gammas_buf: S·C packed (lo u64, hi u64) pairs."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    s = len(xs)
    if len(gammas_buf) != 16 * s * c_chunks:
        raise ValueError("gamma buffer length mismatch")
    import struct

    xbuf = struct.pack(f"<{s}q", *[int(x) for x in xs])
    out_s = ctypes.create_string_buffer(32 * c_chunks * k)
    out_sign = ctypes.create_string_buffer(c_chunks * k)
    rc = lib.ed25519_vss_rlc_scalars(xbuf, gammas_buf, s, c_chunks, k,
                                     out_s, out_sign)
    if rc != 0:
        raise RuntimeError(f"native vss_rlc_scalars failed: {rc}")
    return out_s.raw, out_sign.raw


def decompress_batch(compressed: bytes, n: int) -> Optional[List[ed.Point]]:
    """RFC 8032 decompression of n packed 32-byte points in one native
    call; None if any fails (caller falls back / rejects)."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(compressed) != 32 * n:
        raise ValueError("compressed buffer length mismatch")
    out = ctypes.create_string_buffer(128 * n)
    rc = lib.ed25519_decompress_batch(compressed, n, out)
    if rc != 0:
        return None
    raw = out.raw
    pts: List[ed.Point] = []
    for i in range(n):
        o = raw[128 * i: 128 * (i + 1)]
        x = int.from_bytes(o[:32], "little")
        y = int.from_bytes(o[32:64], "little")
        t = int.from_bytes(o[96:128], "little")
        pts.append((x, y, 1, t))
    return pts


def vss_blind_rows_raw(blinds_buf: bytes, xs: Sequence[int], c_chunks: int,
                       k: int) -> Optional[bytes]:
    """Evaluate all blinding polynomials at all share points mod q.
    blinds_buf: C·k 32-byte little-endian canonical (< q) coefficients;
    returns S·C·32 bytes row-major, or None on invalid share points."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(blinds_buf) != 32 * c_chunks * k:
        raise ValueError("blind buffer length mismatch")
    import struct

    s = len(xs)
    xbuf = struct.pack(f"<{s}q", *[int(x) for x in xs])
    out = ctypes.create_string_buffer(32 * s * c_chunks)
    rc = lib.ed25519_vss_blind_rows(blinds_buf, xbuf, s, c_chunks, k, out)
    if rc != 0:
        return None
    return out.raw


def vss_st_accum(gammas_buf: bytes, rows_buf, blinds_buf,
                 s: int, c_chunks: int) -> Optional[Tuple[int, int]]:
    """(Σγ·row, Σγ·t_val) over all S·C cells — the lhs accumulators of the
    VSS check. rows_buf/blinds_buf may be bytes or C-contiguous numpy
    arrays (int64 rows, uint8 blinds) — passed zero-copy. Returns None if
    any blind value is non-canonical (≥ q)."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    cells = s * c_chunks
    rows_addr, rows_len, keep_r = _buf_addr(rows_buf)
    blinds_addr, blinds_len, keep_b = _buf_addr(blinds_buf)
    if (len(gammas_buf) != 16 * cells or rows_len != 8 * cells
            or blinds_len != 32 * cells):
        raise ValueError("buffer length mismatch")
    out_s = ctypes.create_string_buffer(40)
    out_t = ctypes.create_string_buffer(56)
    rc = lib.ed25519_vss_st_accum(gammas_buf, ctypes.c_void_p(rows_addr),
                                  ctypes.c_void_p(blinds_addr),
                                  s, c_chunks, out_s, out_t)
    del keep_r, keep_b
    if rc != 0:
        return None
    return (int.from_bytes(out_s.raw, "little", signed=True),
            int.from_bytes(out_t.raw, "little"))


def load_xy_sum(xy: bytes, n_batches: int, n: int) -> Optional[bytes]:
    """Fused validate + pointwise sum: n_batches back-to-back batches of
    n×64B affine pairs → the summed n×128B extended batch (msm-ready).
    None if any point is non-canonical or off-curve."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(xy) != 64 * n_batches * n:
        raise ValueError("xy buffer length mismatch")
    out = ctypes.create_string_buffer(128 * n)
    rc = lib.ed25519_load_xy_sum(xy, n_batches, n, out)
    if rc != 0:
        return None
    return out.raw


def load_xy_sum_ptrs(batches: Sequence, n: int) -> Optional[bytes]:
    """load_xy_sum over SEPARATE per-batch buffers (bytes or C-contiguous
    numpy arrays of n×64 bytes each) — no concatenation copy. The miner's
    round intake hands each worker's commitment grid straight from its
    numpy storage; at CNN dims the contiguous form's join alone copies
    hundreds of MB. None if any point is non-canonical or off-curve."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    n_batches = len(batches)
    if n_batches == 0 or n == 0:
        # mirror the native core's rc=1 on degenerate input (and the old
        # contiguous path, which returned None here): callers treat None
        # as "reject", never as an exception
        return None
    ptrs = (ctypes.c_void_p * n_batches)()
    keep = []
    for i, b in enumerate(batches):
        addr, nbytes, ka = _buf_addr(b)
        if nbytes != 64 * n:
            raise ValueError("batch buffer length mismatch")
        ptrs[i] = addr
        keep.append(ka)
    out = ctypes.create_string_buffer(128 * n)
    rc = lib.ed25519_load_xy_sum_ptrs(ptrs, n_batches, n, out)
    del keep
    if rc != 0:
        return None
    return out.raw


def xy_accum(acc: bytearray, xy, n: int) -> Optional[int]:
    """acc[i] += xy[i] over one n×64B affine grid, acc the mutable
    n×128B extended accumulator (initialize with load_xy_batch). Returns
    None on success or the index of the first invalid point, in which
    case acc is UNTOUCHED (validation is a separate first pass) — the
    incremental half of load_xy_sum_ptrs, letting a miner fold each
    worker's commitment grid into the round sum as it arrives."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(acc) != 128 * n:
        raise ValueError("accumulator length mismatch")
    xy_addr, xy_len, keep = _buf_addr(xy)
    if xy_len != 64 * n:
        raise ValueError("xy buffer length mismatch")
    raw = (ctypes.c_char * len(acc)).from_buffer(acc)
    rc = lib.ed25519_xy_accum(ctypes.addressof(raw),
                              ctypes.c_void_p(xy_addr), n)
    del keep, raw
    if rc != 0:
        return rc - 1
    return None


def ext_accum(acc: bytearray, ext: bytes, n: int) -> None:
    """acc[i] += ext[i] pointwise over two n×128B extended buffers — the
    per-wave fold of the incremental intake accumulator."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(acc) != 128 * n or len(ext) != 128 * n:
        raise ValueError("extended buffer length mismatch")
    raw = (ctypes.c_char * len(acc)).from_buffer(acc)
    rc = lib.ed25519_ext_accum(ctypes.addressof(raw),
                               ctypes.c_char_p(ext), n)
    del raw
    if rc != 0:
        raise RuntimeError(f"native ext_accum failed: {rc}")


def scalarmult_noreduce(k: int, p: ed.Point) -> ed.Point:
    """k·P WITHOUT the mod-q reduction the msm wrapper applies — the
    subgroup-membership check ℓ·P == identity needs the full group-order
    scalar to survive (reduced it is 0). k must fit 32 bytes."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    out = ctypes.create_string_buffer(64)
    rc = lib.ed25519_msm(int(k).to_bytes(32, "little"), _point_bytes(p),
                         1, out)
    if rc != 0:
        raise RuntimeError(f"native scalarmult failed: {rc}")
    return point_from_xy64(out.raw)


def msm_signed_raw(scalars_buf: bytes, signs_buf: bytes,
                   points_buf, n: int) -> ed.Point:
    """MSM over pre-packed (magnitude, sign, point) buffers — zero python
    marshalling on the hot path. points_buf may be bytes OR a mutable
    buffer (bytearray/numpy) passed zero-copy — the VSS intake
    accumulator hands its running extended buffer straight in."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    p_addr, p_len, keep = _buf_addr(points_buf)
    if (p_len != 128 * n or len(scalars_buf) != 32 * n
            or len(signs_buf) != n):
        raise ValueError("buffer length mismatch")
    out = ctypes.create_string_buffer(64)
    rc = lib.ed25519_msm_signed(scalars_buf, signs_buf,
                                ctypes.c_void_p(p_addr), n, out)
    del keep
    if rc != 0:
        raise RuntimeError(f"native msm failed: {rc}")
    return point_from_xy64(out.raw)


def msm_raw(scalars: Sequence[int], points_buf: bytes, n: int) -> ed.Point:
    """MSM over an already-validated 128B/point buffer (from
    load_xy_batch) — skips the per-point python int marshalling.

    Scalars may be SIGNED and UNREDUCED (|s| < 2²⁵⁶): short magnitudes keep
    Pippenger's window count down (a mod-q-reduced scalar is dense 252-bit
    even when the underlying combination is ~180-bit), and signs ride a
    separate byte map with on-the-fly point negation in C++."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if len(points_buf) != 128 * n or len(scalars) != n:
        raise ValueError("buffer length mismatch")
    sbuf = bytearray()
    signs = bytearray(n)
    for i, s in enumerate(scalars):
        s = int(s)
        if s < 0:
            signs[i] = 1
            s = -s
        if s >> 256:
            s %= ed.Q
        sbuf += s.to_bytes(32, "little")
    out = ctypes.create_string_buffer(64)
    rc = lib.ed25519_msm_signed(bytes(sbuf), bytes(signs), points_buf, n, out)
    if rc != 0:
        raise RuntimeError(f"native msm failed: {rc}")
    return point_from_xy64(out.raw)


def batch_commit_signed_raw(mags_buf: bytes, signs_buf: bytes,
                            b_buf: bytes, n: int) -> bytes:
    """Pedersen batch commit over pre-packed buffers: mags n×32B LE
    magnitudes (< q), signs n bytes, b n×32B LE canonical blinds. The
    zero-python-marshalling twin of batch_commit_xy."""
    lib = _load()
    assert lib is not None, "native library not built (make -C native)"
    if (len(mags_buf) != 32 * n or len(signs_buf) != n
            or len(b_buf) != 32 * n):
        raise ValueError("buffer length mismatch")
    from biscotti_tpu.crypto.commitments import H_POINT

    out = ctypes.create_string_buffer(64 * n)
    rc = lib.ed25519_batch_commit_signed(mags_buf, signs_buf, b_buf,
                                         _point_bytes(ed.BASE),
                                         _point_bytes(H_POINT), n, out)
    if rc != 0:
        raise RuntimeError(f"native batch_commit failed: {rc}")
    return out.raw


def batch_commit_xy(a: Sequence[int], b: Sequence[int]) -> bytes:
    """[aᵢ·G + bᵢ·H] as a packed n×64B affine (x,y) buffer — worker-side
    VSS coefficient commitments (fixed-base comb path in C++). The affine
    wire format skips both compression here and the sqrt-heavy
    decompression at every verifier. Data scalars travel as
    signed magnitudes so negative quantized coefficients stay a few bytes
    wide instead of dense q−|a| values."""
    if len(a) != len(b):
        raise ValueError("scalar length mismatch")
    n = len(a)
    if n == 0:
        return b""
    mags = bytearray()
    signs = bytearray(n)
    for i, s in enumerate(a):
        v = int(s)
        if not -ed.Q < v < ed.Q:
            v %= ed.Q
        if v < 0:
            signs[i] = 1
            v = -v
        mags += v.to_bytes(32, "little")
    bbuf = b"".join((int(s) % ed.Q).to_bytes(32, "little") for s in b)
    return batch_commit_signed_raw(bytes(mags), bytes(signs), bbuf, n)


