"""The harness end to end on the CPU at a tiny size, with the chip check
lifted here only; a cell, a mix and a per-layer metric
added as new files are found without an edit to any file that is there;
the control and a broken timed path come out as not correct."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def grown(tmp_path):
    """A copy of the benchmark to which a later PR has added a tiny device
    cell and a per-layer metric: new files and new entries only."""
    here = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(here / "traffic" / "device_round_dp.json") as f:
        mix = json.load(f)
    # the measured limits of a new mix on a configuration that is there
    # stand in the mix's own file, under the configuration's name
    mix.update(name="tiny_device", scale={"num_nodes": 24, "why": "a test"},
               trace_seconds=1,
               limits={"emnist_digits_softmax": {"w_next_leaf_gap": 1e-4,
                                                 "err_gap": 0.01}})
    with open(here / "traffic" / "tiny_device.json", "w") as f:
        json.dump(mix, f)
    (here / "layer_metrics" / "rounds_timed.test.py").write_text(
        "def read(record):\n    return record['attempted']\n")
    bench["workloads"].append(
        {"name": "tiny.device", "config": "emnist_digits_softmax",
         "traffic": "tiny_device", "chips": 1, "why": "a test"})
    bench["per_layer"].append(
        {"name": "rounds_timed.test", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "entry points",
         "moves": "setup_s"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_device_driver_end_to_end_and_its_control(grown, monkeypatch):
    result = grown.run_cell("tiny.device", 2**31 + 12345, 1.0, False,
                            require_tpu=False)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"  # and says so

    # the control: the reference in bfloat16 in the program's place
    cell = grown.load_cell("tiny.device")
    driver = grown.load_module("drivers", "device_round")
    from benchmark.compile_meter import CompileMeter

    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.2, trace_dir=None,
                        meter=CompileMeter(), t0=0.0)
    assert all(ok for *_, ok in driver.check(dict(record)))
    control = driver.check(dict(record), control="bfloat16")
    assert not all(ok for *_, ok in control)
    failed = {name for name, *_, ok in control if not ok}
    assert "accept_beyond_ties" in failed  # as on the chip
    # the milder control (bfloat16 storage, float32 sums) runs and gives
    # every number; PERF.md section 2 says what it read on the chip
    mild = driver.check(dict(record), control="bfloat16_f32acc")
    assert {name for name, *_ in mild} == {name for name, *_ in control}

    # the timed path broken underneath: a step that returns its state
    # unchanged has to come out as not correct through the whole run
    from biscotti_tpu.parallel import sim as simmod

    real_init = simmod.Simulator.__init__

    def broken_init(self, *a, **kw):
        real_init(self, *a, **kw)
        real_step = self.round_step

        def stuck(w, stake, it):
            keep_w, keep_stake = w + 0, stake + 0
            _, _, mask, err = real_step(w, stake, it)
            return keep_w, keep_stake, mask, err

        self.round_step = stuck

    monkeypatch.setattr(simmod.Simulator, "__init__", broken_init)
    result = grown.run_cell("tiny.device", 11, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is False


def test_traced_run_reads_the_added_metric_file_and_needs_a_device_plane(
        grown, tmp_path):
    # on the CPU the trace has no device plane: the reduction refuses, and
    # no device number is ever made from a CPU run
    with pytest.raises(RuntimeError, match="device time comes only"):
        grown.run_cell("tiny.device", 5, 0.5, True, require_tpu=False,
                       trace_dir=str(tmp_path / "trace"))
    cell = grown.load_cell("tiny.device")
    assert "rounds_timed.test" in {m["name"] for m in cell["per_layer"]}
    reader = grown.load_module("layer_metrics", "rounds_timed.test")
    assert reader.read({"attempted": 9}) == 9
    # a reader that finds nothing to read returns nothing
    for name in ("round_device_ms.device", "dispatch_gap_ms.device"):
        assert grown.load_module("layer_metrics", name).read({}) is None


def test_no_tpu_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "emnist_softmax.device_round", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "not 'tpu'" in p.stderr


def test_seed_beyond_32_signed_bits_is_folded():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell("emnist_softmax.device_round")
    fields = run.biscotti_fields(cell, 2**31 + 5)
    assert 0 <= fields["seed"] < 2**31 - 1
    assert fields["num_nodes"] == 3383 and fields["noising"] is True
    # a per-layer metric is read in the cells that report what it moves
    assert all(m["name"].endswith(".device") for m in cell["per_layer"])
    # every measured number of the committed cell has its limit on file
    driver = run.load_module("drivers", "device_round")
    assert all(v is not None for v in driver.limits_of(cell).values())
