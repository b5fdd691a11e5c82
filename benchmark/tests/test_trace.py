"""The one reduction from `.xplane.pb` to device numbers, on a small trace
recorded on the v5e (PR 23: three rounds of a 10-peer creditcard
Simulator, the benchmark's own host spans around each call)."""

import gzip
import os
import shutil

import pytest

from benchmark import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture()
def xplane(tmp_path):
    out = tmp_path / "plugins" / "profile" / "2026_09_27" / "v5e.xplane.pb"
    out.parent.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "small_v5e.xplane.pb.gz")) as src, \
            open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def test_reduction_of_the_recorded_v5e_trace(xplane):
    r = trace.reduce_xplane(trace.newest_xplane(xplane))
    assert r["devices"] == 1
    rounds = r["programs"]["jit_round_step"]
    assert len(rounds) == 3 and all(0.01 < ms < 0.1 for ms in rounds)
    # busy is the union of the operations' intervals inside the window
    assert 0 < r["busy_s"] < r["window_s"] < 0.01
    assert sum(s for _, s in r["device_ops"]) <= r["busy_s"] * 1.0001
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    assert all(len(name) <= 120 and " = " not in name
               for name, _ in r["device_ops"])
    # the gaps are named by the benchmark's own host spans
    assert {n for n, _ in r["idle_gaps"]} <= {
        "bench:round_step dispatch", "bench:block_until_ready",
        "host:unattributed"}
    gaps = sum(s for _, s in r["idle_gaps"])
    assert gaps == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)


def test_a_trace_without_a_device_plane_is_refused(xplane):
    with pytest.raises(RuntimeError, match="no /device:GPU plane"):
        trace.reduce_xplane(trace.newest_xplane(xplane),
                            device_prefix="/device:GPU")
    with pytest.raises(FileNotFoundError):
        trace.newest_xplane(os.path.join(xplane, "nothing_here"))


def test_op_name_and_union():
    assert trace.op_name("%fusion.85 = (u32[4]{0}) fusion(...)") == \
        "fusion.85"
    assert trace._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
