"""Accelerator-resident crypto plane (ISSUE 13): property suite + parity.

Three layers, one oracle discipline:

* **limb plane properties** — field mul/add/sub/canonical, point
  add/double, fixed-base, MSM, grid validation, and Shamir recovery are
  property-tested against the python-int oracles in `crypto/ed25519.py`
  / `crypto/commitments.py` / `ops/secretshare.py`, including the
  carry-overflow edge scalars (0, 1, p−1, p, q−1, all-limbs-0xFFFF /
  2²⁵⁶−1);
* **seam parity** — with the plane armed, every PR-6 seam
  (batch_verify_commitments, VssIntakeBatch, batch_schnorr_verify,
  recover_coeffs) must return the CPU path's exact verdict on honest
  AND tampered intakes, with rejection evidence untouched;
* **bit-identity guard** (slow) — a live secure-agg cluster with a
  seeded share-corrupting peer, run CPU vs device: chains, rejection
  evidence (submission_rejected events), and stake debits identical.

Hypothesis drives the property layer when installed; otherwise a
seeded fallback shim with the same @given surface generates
deterministic examples (this container ships no hypothesis and the
constraint is no new deps).
"""

import asyncio
import zlib

import numpy as np
import pytest

from biscotti_tpu.crypto import commitments as cm
from biscotti_tpu.crypto import ed25519 as ed
from biscotti_tpu.crypto import kernels
from biscotti_tpu.crypto.kernels import field as fe
from biscotti_tpu.crypto.kernels import group as gp
from biscotti_tpu.ops import secretshare as ss

pytestmark = pytest.mark.cryptokernel

# ------------------------------------------------- hypothesis-or-shim

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True

    def prop(max_examples=12):
        return settings(max_examples=max_examples, deadline=None)

except ImportError:  # seeded deterministic fallback (no new deps)
    HAVE_HYPOTHESIS = False

    class _Strat:
        def __init__(self, draw):
            self.draw = draw

        def map(self, f):
            return _Strat(lambda r: f(self.draw(r)))

    class st:  # noqa: N801 - mirrors the hypothesis surface we use
        @staticmethod
        def integers(min_value=0, max_value=0):
            return _Strat(lambda r: r.randint(min_value, max_value))

        @staticmethod
        def lists(elem, min_size=0, max_size=8):
            return _Strat(lambda r: [
                elem.draw(r)
                for _ in range(r.randint(min_size, max_size))])

        @staticmethod
        def sampled_from(seq):
            items = list(seq)
            return _Strat(lambda r: r.choice(items))

    def given(**kw):
        def deco(fn):
            import random as _random

            def run(*args):
                base = zlib.crc32(fn.__qualname__.encode())
                for i in range(12):
                    r = _random.Random(base + i)
                    fn(*args, **{k: s.draw(r) for k, s in kw.items()})

            # NOT functools.wraps: the wrapper must present a
            # parameterless signature or pytest reads the strategy
            # kwargs as fixtures
            run.__name__ = fn.__name__
            run.__qualname__ = fn.__qualname__
            run.__doc__ = fn.__doc__
            return run
        return deco

    def prop(max_examples=12):
        def deco(fn):
            return fn
        return deco


EDGE_FIELD = [0, 1, ed.P - 1, ed.P, ed.Q - 1, 2**255 - 1, 2**256 - 1]
EDGE_SCALARS = [0, 1, ed.Q - 1, 2**256 - 1]  # all 8-bit limbs = 255


def _raw_limbs(v: int):
    """32-byte LE encoding → limb row WITHOUT mod-p canonicalization
    (exercises the lazy-carry plane on non-canonical input)."""
    return np.frombuffer(int(v).to_bytes(32, "little"),
                         dtype="<u2").astype(np.int64)[None]


def _canon_int(arr) -> int:
    return fe.limbs_to_int(np.asarray(arr)[0])


# ---------------------------------------------------- field properties


@prop()
@given(a=st.integers(0, 2**256 - 1), b=st.integers(0, 2**256 - 1))
def test_field_ops_match_int_oracle(a, b):
    import jax.numpy as jnp

    al, bl = jnp.asarray(_raw_limbs(a)), jnp.asarray(_raw_limbs(b))
    assert _canon_int(fe.canonical(fe.fmul(fe.carry(al, 2),
                                           fe.carry(bl, 2)))) \
        == (a * b) % ed.P
    assert _canon_int(fe.canonical(fe.fadd(al, bl))) == (a + b) % ed.P
    assert _canon_int(fe.canonical(fe.fsub(al, bl))) == (a - b) % ed.P


@pytest.mark.parametrize("v", EDGE_FIELD)
def test_field_canonical_edges(v):
    import jax.numpy as jnp

    assert _canon_int(fe.canonical(jnp.asarray(_raw_limbs(v)))) == v % ed.P
    # the all-limbs-0xFFFF lazy tensor (not encodable as 32 bytes > 2²⁵⁶
    # after a multiply fold) also canonicalizes exactly
    raw = jnp.asarray(np.full((1, fe.LIMBS), 0xFFFF, np.int64))
    full = sum(0xFFFF << (16 * i) for i in range(fe.LIMBS))
    assert _canon_int(fe.canonical(raw)) == full % ed.P


@prop()
@given(a=st.integers(0, 2**256 - 1), b=st.integers(0, 2**256 - 1),
       c=st.integers(0, 2**256 - 1))
def test_field_chained_ops_keep_loose_invariant(a, b, c):
    """Deep op chains — where a broken lazy-carry bound would silently
    corrupt — still match the oracle, and every intermediate limb stays
    inside the documented loose bound."""
    import jax.numpy as jnp

    al, bl, cl = (jnp.asarray(_raw_limbs(v)) for v in (a, b, c))
    mid = fe.fmul(fe.fsub(fe.fmul(al, bl), cl), fe.fadd(al, cl))
    out = fe.fmul(mid, mid)
    assert int(np.asarray(mid).max()) < (1 << 17)
    expect = pow((a * b - c) * (a + c) % ed.P, 2, ed.P)
    assert _canon_int(fe.canonical(out)) == expect


@prop()
@given(k1=st.integers(1, ed.Q - 1), k2=st.integers(1, ed.Q - 1))
def test_point_add_double_match_oracle(k1, k2):
    p1, p2 = ed.base_mult(k1), ed.base_mult(k2)
    pl = gp.points_to_limbs([p1, p2]).astype(np.int64)
    got_add = gp.limbs_to_point(np.asarray(gp.point_add(pl[:1], pl[1:]))[0])
    assert ed.point_equal(got_add, ed.point_add(p1, p2))
    got_dbl = gp.limbs_to_point(np.asarray(gp.point_double(pl[:1]))[0])
    assert ed.point_equal(got_dbl, ed.point_double(p1))


# --------------------------------------------------------- hot kernels


@pytest.mark.parametrize("k", EDGE_SCALARS + [12345])
def test_fixed_base_matches_oracle(k):
    (got,) = kernels.fixed_base_mult([k])
    assert ed.point_equal(got, ed.base_mult(k))


def test_pedersen_commit_point_matches_oracle():
    got = kernels.pedersen_commit_point(777, 888)
    exp = ed.point_add(ed.base_mult(777),
                       ed.scalar_mult(888, cm.H_POINT))
    assert ed.point_equal(got, exp)


@prop(max_examples=4)
@given(scalars=st.lists(st.sampled_from(
    EDGE_SCALARS + [-5, 7, 2**128 - 1]), min_size=1, max_size=6))
def test_msm_matches_python_oracle(scalars):
    points = [ed.scalar_mult(i + 2, ed.BASE) for i in range(len(scalars))]
    got = kernels.msm(scalars, points)
    exp = cm._msm_python(scalars, points)
    assert ed.point_equal(got, exp)


def test_msm_torsion_parity_with_python_oracle():
    """Commitment-grid cells are on-curve but NOT subgroup-checked, so
    the MSM backends must agree on torsioned points too — s·P and
    (q−s)·(−P) differ by q·P ≠ identity there, which is why the device
    normalization mirrors _msm_python's top-half fold exactly."""
    torsion2 = (0, ed.P - 1, 1, 0)  # (0, −1): order 2, on-curve
    assert cm._xy_to_point(
        (0).to_bytes(32, "little")
        + (ed.P - 1).to_bytes(32, "little")) is not None
    pt = ed.point_add(ed.base_mult(9), torsion2)  # subgroup + torsion
    for s in (ed.Q - 2, ed.Q // 2 + 3, 5, ed.Q - 1):
        got = kernels.msm([s], [pt])
        exp = cm._msm_python([s], [pt])
        assert ed.point_equal(got, exp), f"torsion divergence at s={s}"


def test_msm_empty_and_all_zero():
    assert ed.point_equal(kernels.msm([], []), ed.IDENTITY)
    pts = [ed.BASE, ed.point_double(ed.BASE)]
    assert ed.point_equal(kernels.msm([0, 0], pts), ed.IDENTITY)


def _good_grid(n=3, seed=1):
    a = [seed * 7 + i for i in range(n)]
    b = [seed * 11 + i for i in range(n)]
    raw = cm.batch_pedersen_commit_xy(a, b)
    return np.frombuffer(raw, np.uint8).reshape(n, 64).copy()


def test_grid_validate_matches_cpu_loader():
    g1, g2 = _good_grid(seed=1), _good_grid(seed=2)
    mask, summed = kernels.grid_validate_sum([g1, g2])
    assert mask.tolist() == [True, True]
    for i in range(3):
        exp = ed.point_add(cm._xy_to_point(bytes(g1[i])),
                           cm._xy_to_point(bytes(g2[i])))
        assert ed.point_equal(gp.limbs_to_point(summed[i]), exp)

    # off-curve bit flip: CPU loader rejects the cell, so must the kernel
    bad = g1.copy()
    bad[1, 0] ^= 1
    assert cm._xy_to_point(bytes(bad[1])) is None
    mask2, summed2 = kernels.grid_validate_sum([bad, g2])
    assert mask2.tolist() == [False, True]
    assert ed.point_equal(gp.limbs_to_point(summed2[0]),
                          cm._xy_to_point(bytes(g2[0])))

    # non-canonical coordinate (x + p still encodes in 32 bytes): the
    # CPU loader's x >= P check must be mirrored exactly
    nc = g1.copy()
    x0 = int.from_bytes(bytes(nc[0, :32]), "little")
    nc[0, :32] = np.frombuffer((x0 + ed.P).to_bytes(32, "little"), np.uint8)
    assert cm._xy_to_point(bytes(nc[0])) is None
    mask3, _ = kernels.grid_validate_sum([nc, g2])
    assert mask3.tolist() == [False, True]

    # all grids bad → (mask, None)
    mask4, summed4 = kernels.grid_validate_sum([bad])
    assert mask4.tolist() == [False] and summed4 is None


@prop(max_examples=4)
@given(seed=st.integers(0, 2**31))
def test_shamir_recover_matches_cpu(seed):
    rng = np.random.default_rng(seed)
    q = rng.integers(-10**6, 10**6, 40).astype(np.int64)
    sh = ss.make_shares(q, 10, 20)
    xs = np.asarray(ss.share_xs(20))
    pinv = ss._vandermonde_pinv(tuple(int(x) for x in xs), 10)
    assert np.array_equal(kernels.shamir_recover(pinv, sh),
                          ss.recover_coeffs(sh, xs, 10))


# ------------------------------------------------------- seam parity


@pytest.fixture
def armed():
    kernels.set_enabled(True)
    try:
        yield
    finally:
        kernels.set_enabled(False)


def _intake(d=30, w=4, seed=5):
    rng = np.random.default_rng(seed)
    key = cm.CommitKey.generate(d, label=b"cryptokernel-test")
    items = [(cm.commit_update(q, key), q)
             for q in (rng.integers(-500, 500, d).astype(np.int64)
                       for _ in range(w))]
    entropy = bytes(rng.integers(0, 256, 16 * w, dtype=np.uint8))
    return key, items, entropy


def test_batch_verify_commitments_parity(armed):
    key, items, entropy = _intake()
    kernels.set_enabled(False)
    cpu_good = cm.batch_verify_commitments(items, key, entropy=entropy)
    kernels.set_enabled(True)
    assert cm.batch_verify_commitments(items, key,
                                       entropy=entropy) == cpu_good is True

    bad = list(items)
    bad[2] = (bad[2][0], bad[2][1] + 1)
    kernels.set_enabled(False)
    cpu_bad = cm.batch_verify_commitments(bad, key, entropy=entropy)
    kernels.set_enabled(True)
    assert cm.batch_verify_commitments(bad, key,
                                       entropy=entropy) == cpu_bad is False
    # rejection evidence comes from the CPU bisection, device armed or not
    assert cm.find_bad_commitments(bad, key) == [2]
    # malformed commitment bytes: same early-False either way
    mal = list(items)
    mal[0] = (b"\x01" * 31, mal[0][1])
    assert cm.batch_verify_commitments(mal, key, entropy=entropy) is False


def test_batch_schnorr_verify_parity(armed):
    seeds = [bytes([i]) * 32 for i in range(4)]
    msgs = [b"m%d" % i for i in range(4)]
    trips = [(ed.public_key(s), m, cm.schnorr_sign(s, m))
             for s, m in zip(seeds, msgs)]
    kernels.set_enabled(False)
    assert cm.batch_schnorr_verify(trips) is True
    kernels.set_enabled(True)
    assert cm.batch_schnorr_verify(trips) is True
    bad = list(trips)
    bad[1] = (bad[1][0], b"tampered", bad[1][2])
    kernels.set_enabled(False)
    assert cm.batch_schnorr_verify(bad) is False
    kernels.set_enabled(True)
    assert cm.batch_schnorr_verify(bad) is False


def _vss_instance(seed=7, k=5, c=6, s=4):
    rng = np.random.default_rng(seed)
    chunks = rng.integers(-200, 200, (c, k)).astype(np.int64)
    comms, blinds = cm.vss_commit_chunks(chunks, b"seed" * 8, b"ctx")
    xs = list(range(1, s + 1))
    rows = np.stack([[cm.eval_poly(chunks[ci], x) for ci in range(c)]
                     for x in xs]).astype(np.int64)
    br = cm.vss_blind_rows(blinds, xs)
    ent = bytes(rng.integers(0, 256, 16 * s * c, dtype=np.uint8))
    return comms, rows, br, xs, ent, (s, c, k)


def _vss_run(enabled, members, xs, ent, dims):
    s, c, k = dims
    kernels.set_enabled(enabled)
    acc = cm.VssIntakeBatch(s, c, k, entropy=ent)
    for sid, (comms, rows, br) in members.items():
        assert acc.add(sid, comms, rows, br)
    rejected = acc.fold()
    return rejected, acc.verify(xs), sorted(acc.members())


def test_vss_intake_parity(armed):
    comms, rows, br, xs, ent, dims = _vss_instance()
    members = {1: (comms, rows, br), 2: (comms, rows, br)}
    assert _vss_run(False, members, xs, ent, dims) \
        == _vss_run(True, members, xs, ent, dims) == ([], True, [1, 2])

    # off-curve grid: evicted at fold, identically
    badc = comms.copy()
    badc[0, 0, 0] ^= 1
    members = {1: (comms, rows, br), 2: (badc, rows, br)}
    assert _vss_run(False, members, xs, ent, dims) \
        == _vss_run(True, members, xs, ent, dims) == ([2], True, [1])

    # corrupted share row: settle False, identically (per-member CPU
    # fallback identification is the runtime's, untouched here)
    rows_bad = rows.copy()
    rows_bad[0, 0] += 1
    members = {1: (comms, rows_bad, br)}
    assert _vss_run(False, members, xs, ent, dims) \
        == _vss_run(True, members, xs, ent, dims) == ([], False, [1])


def test_vss_device_fault_fails_over_to_cpu(armed, monkeypatch):
    """A device kernel FAULT (not a verdict) mid-batch must not fail
    the round: the accumulator rebuilds from the retained grids and the
    batch finishes on the CPU path with the same verdict."""
    comms, rows, br, xs, ent, dims = _vss_instance(seed=21)
    s, c, k = dims
    acc = cm.VssIntakeBatch(s, c, k, entropy=ent)
    assert acc.add(1, comms, rows, br)
    assert acc.fold() == []  # first wave folds on device
    assert acc._acc_dev is not None
    # second wave hits a faulting device plane
    assert acc.add(2, comms, rows, br)
    with monkeypatch.context() as m:
        m.setattr(kernels, "grid_validate_sum",
                  lambda grids: (_ for _ in ()).throw(
                      RuntimeError("backend fault")))
        assert acc.fold() == []
    assert acc._dev_failed and acc._acc_dev is None
    assert acc.verify(xs) is True  # CPU settle over the rebuilt acc
    # oracle: the same members through an all-CPU batch agree
    kernels.set_enabled(False)
    ref = cm.VssIntakeBatch(s, c, k, entropy=ent)
    assert ref.add(1, comms, rows, br) and ref.add(2, comms, rows, br)
    ref.fold()
    assert ref.verify(xs) is True

    # a fault at SETTLE time (device folds succeeded) also recovers
    kernels.set_enabled(True)
    acc2 = cm.VssIntakeBatch(s, c, k, entropy=ent)
    assert acc2.add(1, comms, rows, br)
    assert acc2.fold() == [] and acc2._acc_dev is not None
    monkeypatch.setattr(kernels, "msm",
                        lambda *a, **kw: (_ for _ in ()).throw(
                            RuntimeError("backend fault")))
    assert acc2.verify(xs) is True
    assert acc2._dev_failed


def test_compiler_refusal_is_not_a_device_fault(armed, monkeypatch):
    """A kernel the backend's compiler refuses must not fail over to the
    CPU and report a device run: CompileError propagates from every
    seam, and arming a plane whose probe was refused is an error."""
    comms, rows, br, xs, ent, dims = _vss_instance(seed=22)
    acc = cm.VssIntakeBatch(*dims, entropy=ent)
    assert acc.add(1, comms, rows, br)

    def refuse(*a, **kw):
        raise kernels.CompileError("UNIMPLEMENTED: s64 dot")

    monkeypatch.setattr(kernels, "grid_validate_sum", refuse)
    with pytest.raises(kernels.CompileError):
        acc.fold()
    assert not acc._dev_failed

    kernels.set_enabled(False)
    monkeypatch.setattr(kernels, "_avail", False)
    monkeypatch.setattr(kernels, "_avail_reason", "UNIMPLEMENTED: s64 dot")
    with pytest.raises(RuntimeError, match="UNIMPLEMENTED: s64 dot"):
        kernels.set_enabled(True)
    assert not kernels.active()


def test_recover_coeffs_parity(armed):
    rng = np.random.default_rng(11)
    q = rng.integers(-1000, 1000, 40).astype(np.int64)
    sh = ss.make_shares(q, 10, 20)
    xs = np.asarray(ss.share_xs(20))
    kernels.set_enabled(False)
    cpu = ss.recover_coeffs(sh, xs, 10)
    kernels.set_enabled(True)
    assert np.array_equal(ss.recover_coeffs(sh, xs, 10), cpu)


# ------------------------------------------- arming / config / metrics


def test_device_crypto_defaults_off_and_rides_the_cli():
    import argparse

    from biscotti_tpu.config import BiscottiConfig

    assert BiscottiConfig().device_crypto is False, \
        "--device-crypto must default to the CPU path"
    ap = argparse.ArgumentParser()
    BiscottiConfig.add_args(ap)
    ns = ap.parse_args(["--device-crypto", "1"])
    assert BiscottiConfig.from_args(ns).device_crypto is True


def test_disarmed_plane_is_never_consulted():
    kernels.set_enabled(False)
    assert cm._device_mod() is None
    assert ss._device_kernels() is None
    assert not kernels.active()


def test_kernel_instrumentation_emits_metric_and_span(armed):
    from biscotti_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    spans = []

    class _Cm:
        def __init__(self, kernel):
            self.kernel = kernel

        def __enter__(self):
            spans.append(self.kernel)

        def __exit__(self, *exc):
            return False

    kernels.set_metrics_registry(reg)
    kernels.set_span_hook(_Cm)
    try:
        kernels.grid_validate_sum([_good_grid(seed=9)])
    finally:
        kernels.set_metrics_registry(None)
        kernels.set_span_hook(None)
    snap = reg.snapshot()
    assert "biscotti_crypto_device_seconds" in snap
    labels = [row["labels"] for row in
              snap["biscotti_crypto_device_seconds"]["series"]]
    assert {"kernel": "grid_validate"} in labels
    assert "grid_validate" in spans
    assert kernels.device_calls().get("grid_validate", 0) >= 1


def test_prewarm_suppression_is_thread_local():
    """Concurrent per-peer prewarms must not silence other threads'
    instrumentation (the module-global flag raced its restore and left
    the whole process suppressed — observed as a live cluster reporting
    zero kernel calls)."""
    import threading

    from biscotti_tpu.crypto.kernels import instrument

    before = instrument.device_calls().get("probe", 0)
    hold = threading.Event()
    release = threading.Event()

    def suppressed_worker():
        with instrument.suppressed():
            with instrument.timed("probe"):
                pass  # silenced
            hold.set()
            release.wait(5)

    t = threading.Thread(target=suppressed_worker)
    t.start()
    assert hold.wait(5)
    # while the other thread sits inside suppressed(), THIS thread's
    # instrumentation still records
    with instrument.timed("probe"):
        pass
    release.set()
    t.join(5)
    after = instrument.device_calls().get("probe", 0)
    assert after == before + 1  # exactly the unsuppressed call


def test_native_degrades_loudly_and_python_parity(capsys, monkeypatch):
    """Satellite: a missing/stale libbiscotti_native.so must announce
    itself ONCE with the `make -C native` target named, and the
    pure-Python fallback must agree with the native backend."""
    from biscotti_tpu.crypto import _native

    # parity first (with whatever backend is live): python vs dispatch
    scalars = [3, 5, 2**200 + 7]
    points = [ed.scalar_mult(i + 2, ed.BASE) for i in range(3)]
    assert ed.point_equal(cm._msm_python(scalars, points),
                          cm.msm(scalars, points))

    monkeypatch.setenv("BISCOTTI_NO_NATIVE_BUILD", "1")
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_load_attempted", False)
    monkeypatch.setattr(_native, "_load_error", "")
    monkeypatch.setattr(_native, "_LIB_PATHS",
                        ["/nonexistent/libbiscotti_native.so"])
    assert _native.available() is False
    err = capsys.readouterr().err
    assert "make -C native" in err and "pure-Python" in err
    assert "libbiscotti_native.so" in _native.load_error()
    # degraded, the full dispatch path still answers correctly
    assert ed.point_equal(cm.msm(scalars, points),
                          cm._msm_python(scalars, points))
    # and the announcement fired once, not per call
    assert _native.available() is False
    assert capsys.readouterr().err == ""


def test_profile_round_splits_crypto_residency():
    """The overlap collector reports crypto_cpu vs crypto_device from
    the span stream, without double-charging the nested device span
    into serial_s."""
    from biscotti_tpu.tools import profile_round as pr

    class _Rec:
        def __init__(self, events):
            self._ev = events

        def tail(self, n):
            return self._ev

    class _Tele:
        def __init__(self, events):
            self.recorder = _Rec(events)

    class _Agent:
        def __init__(self, events):
            self.tele = _Tele(events)

    ev = [
        {"event": "round_start", "node": 0, "iter": 1, "mono": 0.0},
        {"event": "span", "node": 0, "iter": 1, "phase": "miner_verify",
         "dur_s": 1.0, "mono": 1.0},
        {"event": "span", "node": 0, "iter": 1, "phase": "crypto_device",
         "dur_s": 0.8, "mono": 1.0},
        {"event": "round_end", "node": 0, "iter": 2, "height": 1,
         "mono": 2.0},
    ]
    table = pr.collect_round_table([_Agent(ev)])
    # the device span is nested inside miner_verify, so its seconds are
    # SUBTRACTED from the host side: cpu 1.0 − device 0.8 = 0.2 stayed
    # on the CPU, and the rows sum to the crypto phase time
    assert table["crypto_split_s"] == {"crypto_cpu": 0.2,
                                       "crypto_device": 0.8}
    # nested device span is NOT double-charged into serial work
    assert table["rounds"][0]["serial_s"] == 1.0


def test_chaos_report_records_crypto_path():
    from biscotti_tpu.tools import chaos

    class NS:
        device_crypto = 1

    results = [{"telemetry": {"device_crypto": {
        "enabled": True, "active": True,
        "seconds": {"msm": 1.25}, "calls": {"msm": 3}}}}]
    rep = chaos._device_crypto_report(NS, results)
    assert rep["path"] == "device" and rep["kernel_calls"] == {"msm": 3}
    rep_off = chaos._device_crypto_report(
        type("NS2", (), {"device_crypto": 0}), results)
    assert rep_off == {"enabled": False, "path": "cpu"}
    # armed but the plane never ran a kernel → degraded, visibly
    idle = [{"telemetry": {"device_crypto": {
        "enabled": True, "active": False, "seconds": {}, "calls": {}}}}]
    assert chaos._device_crypto_report(NS, idle)["path"] == "cpu (degraded)"


# ------------------------------------------------- live guard (slow)


@pytest.mark.slow
def test_device_crypto_live_bit_identity_guard():
    """ISSUE 13 acceptance: one seeded live secure-agg cluster with a
    share-corrupting Byzantine peer, run twice — CPU path vs
    --device-crypto — must produce identical chains, identical
    rejection evidence (submission_rejected events, reason included),
    and identical stake debits. The device run's kernels must actually
    have executed (device seconds > 0)."""
    from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts
    from biscotti_tpu.runtime.peer import PeerAgent
    from biscotti_tpu.tools import chaos

    # pre-warm the jit caches at the bucket shapes the cluster will hit,
    # so round deadlines race steady-state kernels, not XLA compiles
    kernels.set_enabled(True)
    try:
        _vss_run(True, {1: _vss_instance(seed=1)[0:3]},
                 *_vss_instance(seed=1)[3:])
    finally:
        kernels.set_enabled(False)

    class CorruptSharePeer(PeerAgent):
        def _secret_arrays(self, shares, blind_rows, comms, sl):
            arrays = super()._secret_arrays(shares, blind_rows, comms, sl)
            arrays["share_rows"] = arrays["share_rows"] + 12345
            return arrays

    n = 5
    wide = Timeouts(update_s=25.0, block_s=90.0, krum_s=20.0,
                    share_s=25.0, rpc_s=25.0)

    def run(port, device):
        def cfg(i):
            return BiscottiConfig(
                node_id=i, num_nodes=n, dataset="creditcard",
                base_port=port, num_verifiers=1, num_miners=1,
                num_noisers=1, secure_agg=True, noising=False,
                verification=True, defense=Defense.NONE,
                max_iterations=1, convergence_error=0.0,
                sample_percent=1.0, batch_size=8, timeouts=wide, seed=3,
                pipeline=True, batch_intake=True,
                device_crypto=device)

        from biscotti_tpu.parallel import roles as R
        from biscotti_tpu.ledger.chain import Blockchain

        chain = Blockchain(50, n, 10)
        verifiers, miners = R.elect_committees(
            chain.latest_stake_map(), chain.latest_hash(), 1, 1, n)
        byz = max(i for i in range(n)
                  if i not in set(verifiers) | set(miners))

        async def go():
            agents = [CorruptSharePeer(cfg(i)) if i == byz
                      else PeerAgent(cfg(i)) for i in range(n)]
            results = await asyncio.gather(*(a.run() for a in agents))
            return results, agents

        try:
            results, agents = asyncio.run(go())
        finally:
            kernels.set_enabled(False)
        honest = [(r, a) for r, a in zip(results, agents) if a.id != byz]
        dumps = [r["chain_dump"] for r, _ in honest]
        assert all(d == dumps[0] for d in dumps)
        evidence = sorted(
            (a.id, ev.get("source"), ev.get("reason"))
            for _, a in honest
            for ev in a.tele.recorder.tail(100000)
            if ev.get("event") == "submission_rejected")
        stake = honest[0][1].chain.latest_stake_map()
        return byz, dumps[0], evidence, stake

    byz_c, dump_c, ev_c, stake_c = run(15210, False)
    byz_d, dump_d, ev_d, stake_d = run(15240, True)
    assert byz_c == byz_d
    assert dump_c == dump_d, "device chain diverged from the CPU chain"
    assert ev_c == ev_d, "rejection evidence diverged"
    assert stake_c == stake_d and stake_c[byz_c] < 10, \
        "stake debits diverged (or the cheat went undebited)"
    assert ev_c, "the Byzantine peer was never rejected"
    secs = kernels.device_seconds()
    assert any(v > 0 for v in secs.values()), \
        "device run never executed a kernel"