"""The seam between a model and the simulator's metrics page (PR 46): a
language model DECLARES its static gauges (models/lm.py:
`info["gauges"]`; ops/moe.py: `GAUGES`, one a key of `dispatch_stats`) and
`Simulator.run` publishes what is declared and names none. A fake model's
rows reach the page, `ops/moe.dispatch_stats` is the arithmetic the
simulator's own was, and each tiny preset's page is, family by family, the
text the parent commit rendered (tests/lm_gauges_63bc454.txt: `# HELP`, `#
TYPE` and the samples of every `biscotti_{lm,attn,ssm,gdn,moe,sim}_*`
family after two rounds, read on 63bc454 before the gauges moved out of
parallel/sim.py; the one clock's samples left out; Qwen3-Next's page with
the one row PR 49 declared, `biscotti_gdn_walked_layers`). A gauge renamed,
dropped or re-worded fails here."""

import dataclasses
import os
import re

import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import moe
from biscotti_tpu.parallel.sim import Simulator
from biscotti_tpu.telemetry import MetricsRegistry

from lm_family import cfg_of

DECLARED = re.compile(r"^(# (HELP|TYPE) )?biscotti_(lm|attn|ssm|gdn|moe|sim)_")
CLOCK = "biscotti_sim_round_seconds"


def test_the_simulator_publishes_what_a_model_declares():
    """Two rows over a classifier's step, one with a label and one whose
    value is a function of the run's start (called once, before the first
    round, with the starting parameters, the held-out rows and the frozen
    tree): exactly those beside the simulator's own families."""
    calls = []

    def of_the_start(params, x_val, frozen):
        calls.append((params, x_val.shape, frozen))
        return 0.25

    real = model_for_dataset("creditcard")
    fake = dataclasses.replace(real, info={"gauges": [
        ("biscotti_fake_share", "a share a kind", 0.5, {"kind": "odd"}),
        ("biscotti_fake_start", "a function of the run's start",
         of_the_start, {})]})
    registry = MetricsRegistry()
    sim = Simulator(BiscottiConfig(dataset="creditcard", num_nodes=10,
                                   seed=3, defense=Defense.KRUM),
                    model=fake, metrics=registry)
    sim.run(2, stop_at_convergence=False)
    page = registry.render().splitlines()
    assert "# HELP biscotti_fake_share a share a kind" in page
    assert 'biscotti_fake_share{kind="odd"} 0.5' in page
    assert "# HELP biscotti_fake_start a function of the run's start" in page
    assert "biscotti_fake_start 0.25" in page
    families = {line.split()[2] for line in page if line.startswith("# TYPE")}
    assert {name for name in families
            if not name.startswith("biscotti_sim_")} == {
        "biscotti_fake_share", "biscotti_fake_start"}
    assert len(calls) == 1
    params, held_out, frozen = calls[0]
    assert (held_out, frozen) == (sim.x_val.shape, {})
    assert real.flatten(params).shape == (real.num_params,)


# a round's counts of a plain router and of a group-limited one (the tiny
# presets' first round walked in two blocks of two peers), and what
# `Simulator.dispatch_stats` made of them on 63bc454, where the arithmetic
# was the simulator's own
COUNTED = {
    "laguna_tiny": (
        {"buffer_rows": [192, 192], "dropped": [0, 0],
         "grouped_kernel": [0, 0],
         "load": [[17, 11, 37, 32], [40, 6, 22, 13]],
         "tile_rows": [4096, 4096], "uncut": [0, 0]},
        {"assignments_held": 178.0,
         "load_max_over_mean": 1.9753086419753085, "tokens_dropped": 0.0,
         "tile_fill": 0.021728515625, "grouped_kernel": 0.0,
         "uncut_calls": 0.0, "buffer_rows": 96.0}),
    "deepseek_v2_tiny": (
        {"buffer_rows": [192, 192], "dropped": [0, 0],
         "grouped_kernel": [0, 0], "groups_spanned": [254, 253],
         "load": [[40, 19, 22, 31], [23, 24, 20, 23]],
         "tile_rows": [4096, 4096], "tokens": [128, 128], "uncut": [0, 0]},
        {"groups_kept": 1.98046875, "assignments_held": 202.0,
         "load_max_over_mean": 1.4285714285714286, "tokens_dropped": 0.0,
         "tile_fill": 0.024658203125, "grouped_kernel": 0.0,
         "uncut_calls": 0.0, "buffer_rows": 96.0}),
}


@pytest.mark.parametrize("name", sorted(COUNTED))
def test_the_routing_arithmetic_is_what_the_simulators_was(name):
    """`moe.dispatch_stats` over recorded counts, alone and as
    `Simulator.dispatch_stats` calls it (four sampled peers in blocks of
    two), gives the parent's numbers, every key has its gauge, and counts
    of no router give nothing."""
    counts, want = COUNTED[name]
    assert moe.dispatch_stats(counts, 2.0) == want
    sim = Simulator(cfg_of(name, batch_size=2))
    sim.steps.block = 2
    assert (sim.cfg.num_samples, sim.peer_block) == (4, 2)
    assert sim.dispatch_stats(counts) == want
    assert set(want) <= set(moe.GAUGES)
    assert moe.dispatch_stats({}, 2.0) == {} == sim.dispatch_stats({})


def _recorded():
    """{preset: the lines recorded for it}."""
    pages, name = {}, None
    path = os.path.join(os.path.dirname(__file__), "lm_gauges_63bc454.txt")
    with open(path) as f:
        for line in f.read().splitlines():
            if line.startswith("## "):
                name = line[3:]
                pages[name] = []
            else:
                pages[name].append(line)
    return pages


def _sample(line):
    """(a line's text less its value, the value): a `# HELP` or `# TYPE`
    line is compared whole, a sample by its name and labels and, to a part
    in a million (the page prints floats in full), its value."""
    if line.startswith("#"):
        return line, None
    series, value = line.rsplit(" ", 1)
    return series, float(value)


@pytest.mark.parametrize("name", ["laguna_tiny", "deepseek_v2_tiny",
                                  "granite_h_tiny", "qwen3_next_tiny",
                                  "mimo_v2_tiny"])
def test_a_tiny_presets_page_is_what_the_parent_rendered(name):
    registry = MetricsRegistry()
    Simulator(cfg_of(name, batch_size=2), metrics=registry).run(
        2, stop_at_convergence=False)
    got = [_sample(line) for line in registry.render().splitlines()
           if DECLARED.match(line) and not line.startswith(CLOCK)]
    want = [_sample(line) for line in _recorded()[name]]
    assert [text for text, _ in got] == [text for text, _ in want]
    for (text, value), (_, recorded) in zip(got, want):
        assert value == pytest.approx(recorded, rel=1e-6), text
