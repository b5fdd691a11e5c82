"""PR 35's one per-layer metric, `peer_block.device`: the reader hands on
what the three language-model drivers record, the three cells read it and
the softmax cell (no `step_bytes`: all its peers at once) does not, and
it was appended after everything PR 33's file had."""

import json
import os

import pytest

from test_harness import ROOT, load_run

METRIC = "peer_block.device"
CELLS = ["laguna_fedlora.device_round", "deepseek_v2_fedlora.device_round",
         "granite_h_fedlora.device_round"]


@pytest.fixture(scope="module")
def run():
    return load_run(os.path.join(ROOT, "benchmark"))


@pytest.mark.parametrize("record,block", [
    ({"peer_block": 3}, 3), ({"peer_block": 1, "moe": {}}, 1),
    ({}, None), ({"moe": {"load_max_over_mean": [3.0]}}, None)])
def test_the_reader_returns_the_records_block(run, record, block):
    """None where the record has none (a driver without the key, or the
    parent's program under this PR's benchmark files): the harness then
    leaves the metric out and does not raise."""
    assert run.load_module("layer_metrics", METRIC).read(record) == block


def test_the_language_model_cells_read_it_and_no_other(run):
    for cell in CELLS:
        found = run.load_cell(cell)["per_layer"]
        assert [m for m in found if m["name"] == METRIC], cell
    softmax = run.load_cell("emnist_softmax.device_round")["per_layer"]
    assert METRIC not in {m["name"] for m in softmax}


def test_the_entry_was_appended_after_what_was_there():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    at = [m["name"] for m in per_layer].index(METRIC)
    assert at >= 34  # PR 33's file had 34 per-layer metrics
    assert per_layer[at] == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device round",
        "moves": "device_round_ms", "workloads": CELLS}
