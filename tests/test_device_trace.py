"""The device round measured from inside (docs/OBSERVABILITY.md, "Device
trace"): the STAGES scopes in both round programs, `round_hlo()` past a
compile cache that holds the unscoped program, the program's spans in a
profiler trace under `biscotti:`, and the one timing body they share.
CPU: what is asserted is names and counts, never a time."""

import contextlib
import glob
import os
import re
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import deepseek_v2, granite_hybrid, laguna
from biscotti_tpu.parallel import sim as sim_module
from biscotti_tpu.parallel.sim import (STAGES, Simulator,
                                       sharded_round_step_fn)
from biscotti_tpu.telemetry import Telemetry
from biscotti_tpu.utils import profiling
from biscotti_tpu.utils.profiling import PhaseClock, device_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    """Krum + DP noise on a small cluster: every stage has work."""
    base = dict(dataset="creditcard", num_nodes=10, num_verifiers=1,
                num_miners=1, sample_percent=0.6, batch_size=10,
                epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, seed=3)
    base.update(kw)
    return BiscottiConfig(**base)


@pytest.fixture(scope="module")
def sim():
    return Simulator(_cfg())


@pytest.fixture(scope="module")
def round_hlo(sim):
    return sim.round_hlo()


@pytest.fixture(scope="module")
def sharded_text():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("peers",))
    eight = Simulator(_cfg(num_nodes=8))  # every peer contributes: 2 a chip
    w = jax.ShapeDtypeStruct((eight.num_params,), jnp.float32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return sharded_round_step_fn(eight, mesh).lower(
        w, eight.x, eight.y, scalar, scalar).as_text(debug_info=True)


def _names(text, stage):
    """`stage` as a whole token of a scope path, the way the join of
    `benchmark/stages.py` matches it."""
    return re.search(rf"(?<![\w]){stage}(?![\w])", text)


def test_stage_names_are_tokens_of_their_own():
    """No name is part of another, so the last token of an `op_name` is
    one stage and never two."""
    assert len(set(STAGES)) == len(STAGES) == 10
    assert not [(a, b) for a in STAGES for b in STAGES
                if a != b and a in b]


@pytest.mark.parametrize("stage", STAGES)
def test_every_stage_is_named_in_round_hlo(round_hlo, stage):
    """The optimized program carries the scope in some instruction's
    `op_name`, after the jit's own name."""
    assert _names(round_hlo, stage), stage
    assert 'op_name="jit(round_step)/' in round_hlo


# the sharded step keeps no stake ledger, and with the fault plane off it
# drops no frame: `round_ledger` has nothing to wrap there
@pytest.mark.parametrize("stage",
                         [s for s in STAGES if s != "round_ledger"])
def test_every_stage_is_named_in_the_sharded_program(sharded_text, stage):
    assert _names(sharded_text, stage), stage


def test_round_hlo_touches_no_buffer_and_restores_the_cache_switch(sim):
    w, stake = sim.init_state()
    was = jax.config.jax_enable_compilation_cache
    text = sim.round_hlo()
    assert jax.config.jax_enable_compilation_cache == was
    assert "HloModule jit_round_step" in text
    # nothing was donated: the state is still there to be read
    assert not w.is_deleted() and not stake.is_deleted()
    w, stake, mask, _ = sim.round_step(w, stake, 0)
    assert int(mask.sum()) == 6 - 3


def test_round_hlo_carries_the_scopes_past_a_cache_of_the_unscoped_program(
        tmp_path, monkeypatch):
    """The persistent cache's key ignores scope metadata, so a cache filled
    by a tree without the scopes hands back ITS executable, and that
    executable's text: `round_hlo()` compiles outside the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    def lowered_text(simulator):
        """What a caller that trusted the cache would read."""
        w, stake = simulator.init_state()
        args = [w, stake, 0, jnp.asarray(simulator.cfg.seed, jnp.int32),
                simulator.x, simulator.y, simulator.x_val, simulator.y_val]
        return simulator._round_step_jit.lower(*args).compile().as_text()

    def entries():
        return set(glob.glob(str(tmp_path / "*round_step*")))

    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        cfg = _cfg(seed=4, num_nodes=9)
        with monkeypatch.context() as patched:
            patched.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
            unscoped = lowered_text(Simulator(cfg))
        assert "round_gather" not in unscoped
        # as in another process: what was traced without the scopes
        # (the jitted krum_accept_mask) is forgotten, the files stay
        jax.clear_caches()
        filled = entries()
        assert filled, "the unscoped program was not written to the cache"
        scoped = Simulator(cfg)
        if "round_gather" in lowered_text(scoped):
            pytest.skip("this backend's cache key tells the scopes apart")
        assert entries() == filled  # a hit: nothing new was written
        text = scoped.round_hlo()
        assert all(stage in text for stage in STAGES)
        assert entries() == filled  # and round_hlo() wrote nothing either
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def _host_events(trace_dir, prefix=profiling.TRACE_PREFIX):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1, paths
    names = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [ev.name for ev in line.events
                          if ev.name.startswith(prefix)]
    return names


def test_round_spans_reach_a_profiler_trace(sim, tmp_path):
    w, stake = sim.init_state()
    w, stake, _, _ = sim.round_step(w, stake, 0)  # compiled before the trace
    before = dict(sim.phases.counts)
    with device_trace(str(tmp_path)):
        for it in range(1, 4):
            w, stake, _, _ = sim.round_step(w, stake, it)
        jax.block_until_ready(w)
    names = _host_events(str(tmp_path))
    assert names.count("biscotti:sim.round.args") == 3
    assert names.count("biscotti:sim.round.dispatch") == 3
    # the same body charged the clock: one call, one span
    for phase in ("sim.round.args", "sim.round.dispatch"):
        assert sim.phases.counts[phase] - before[phase] == 3


def test_telemetry_span_reaches_a_profiler_trace(tmp_path):
    """Every span the live path has lands in a device trace with no new
    call site: `Telemetry.span` times through `PhaseClock.phase`."""
    assert "jax" in sys.modules
    tel = Telemetry(node=7)
    with device_trace(str(tmp_path)):
        with tel.span("crypto_commit", it=2):
            pass
        with pytest.raises(RuntimeError):
            with tel.span("share_gen", it=2):
                raise RuntimeError("a span that fails is still a span")
    names = _host_events(str(tmp_path))
    assert names.count("biscotti:crypto_commit") == 1
    assert names.count("biscotti:share_gen") == 1
    # and the three sinks it always fed got the same one timing
    assert tel.phases.counts == {"crypto_commit": 1, "share_gen": 1}
    spans = [e for e in tel.recorder.tail(10) if e["event"] == "span"]
    assert [e["phase"] for e in spans] == ["crypto_commit", "share_gen"]
    assert spans[0]["dur_s"] == round(tel.phases.totals["crypto_commit"], 6)
    assert "biscotti_phase_seconds" in tel.render()


def test_annotation_is_a_nullcontext_without_jax():
    """`telemetry` and `utils/profiling` stay stdlib-only: a process that
    has not imported jax gets no annotation, and is not made to import it."""
    code = (
        "import contextlib, sys\n"
        "from biscotti_tpu.utils import profiling\n"
        "from biscotti_tpu.telemetry import Telemetry\n"
        "assert isinstance(profiling.annotation('x'), "
        "contextlib.nullcontext)\n"
        "tel = Telemetry()\n"
        "with tel.span('crypto_commit', it=1): pass\n"
        "assert tel.phases.counts == {'crypto_commit': 1}\n"
        "bad = [m for m in ('jax', 'numpy') if m in sys.modules]\n"
        "assert not bad, bad\n")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT)


def test_device_trace_leaves_pythons_tracer_off(monkeypatch, tmp_path):
    seen = {}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, profiler_options=None: seen.update(
                            dir=d, options=profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with device_trace(str(tmp_path)):
        pass
    assert seen["dir"] == str(tmp_path)
    assert seen["options"].python_tracer_level == 0


def test_phase_clock_add_from_eight_threads_loses_no_call():
    clock, n = PhaseClock(), 20000
    start = threading.Barrier(8)

    def work():
        start.wait(timeout=30)
        for _ in range(n):
            clock.add("shard_draw", 0.5)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert clock.counts == {"shard_draw": 8 * n}
    assert clock.totals == {"shard_draw": 8 * n * 0.5}


def test_phase_yields_its_timing():
    clock = PhaseClock()
    with clock.phase("recovery") as timing:
        assert timing.seconds == 0.0
    assert timing.seconds > 0.0
    assert clock.totals == {"recovery": timing.seconds}
    with pytest.raises(KeyError):
        with clock.phase("recovery"):
            raise KeyError("charged all the same")
    assert clock.counts == {"recovery": 2}


def test_simulator_carries_its_set_up_phases():
    fresh = Simulator(_cfg(seed=5))
    summary = fresh.phases.summary()
    assert set(summary) == {"sim.shards", "sim.stack", "sim.to_device",
                            "sim.build", "sim.frozen"}  # the last in build
    assert all(row["calls"] == 1 for row in summary.values())
    w, stake = fresh.init_state()
    fresh.round_step(w, stake, 0)
    assert fresh.phases.counts["sim.round.args"] == 1
    assert fresh.phases.counts["sim.round.dispatch"] == 1


def test_the_loader_clock_counts_every_draw():
    name = "creditcard7041"  # a peer no other test asks for
    before = ds.CLOCK.counts.get("shard_draw", 0)
    ds.load_shard("creditcard", name)
    assert ds.CLOCK.counts["shard_draw"] == before + 1
    ds.load_shard("creditcard", name)  # cached: nothing is drawn
    assert ds.CLOCK.counts["shard_draw"] == before + 1


def test_sim_module_keeps_the_vocabulary_where_the_readers_look():
    """`benchmark/stages.py` imports nothing of the program: it finds
    STAGES on the module that defines the traced object."""
    assert sys.modules[Simulator.__module__] is sim_module
    assert sim_module.STAGES is STAGES


# ---- the walked round books its own work (PR 36): `peer_walk` around
# `lm.peer_at_a_time`, the attention block in parts (`SUBSCOPES`)

LM_TINY = dict(dataset="lm_tokens_tiny", num_nodes=8, batch_size=2,
               sample_percent=1.0, learning_rate=0.1, grad_clip=1.0, seed=0)
# model name of `lm_tokens_tiny` -> the scope its parts are opened inside
COARSE = {"": "lm_attention", "deepseek_v2_tiny": "mla_proj"}


def _vocabulary(sim):
    module = sys.modules[type(sim.model.info["config"]).__module__]
    return module.SCOPES, module.SUBSCOPES


@pytest.fixture(scope="module")
def walked_names():
    """{model: (every `op_name` of `round_hlo()`, SCOPES, SUBSCOPES)}, the
    six sampled peers stepped three a block: only the `op_name`s, because
    the text's tables also hold file and function names (this file's)."""
    found = {}
    for model in COARSE:
        walked = Simulator(_cfg(**dict(LM_TINY, model_name=model)))
        walked.steps.block = 3  # before the program is traced
        found[model] = (re.findall(r'op_name="([^"]*)"', walked.round_hlo()),
                        *_vocabulary(walked))
    return found


def _tokens(op_name, vocabulary):
    return re.findall(r"(?<![\w])(?:%s)(?![\w])" % "|".join(vocabulary),
                      op_name)


@pytest.mark.parametrize("model,scope", [
    (model, scope) for model, module in (("", laguna),
                                         ("deepseek_v2_tiny", deepseek_v2))
    for scope in ("peer_walk",) + module.SUBSCOPES])
def test_the_walk_and_every_part_are_named_in_round_hlo(walked_names, model,
                                                        scope):
    names, scopes, parts = walked_names[model]
    assert scope in scopes + parts
    assert any(_names(name, scope) for name in names), scope


@pytest.mark.parametrize("model", sorted(COARSE))
def test_a_part_stands_inside_its_scope_and_the_walk_computes_nothing(
        walked_names, model):
    """Under SCOPES alone an instruction of a part still reads as the
    coarse scope (its token stands BEFORE the part's, so the last SCOPES
    token of the `op_name` is the coarse one and every reader that was
    reads what it read); and what the loop's body computes re-opens its
    scope inside the body, so the walk's own token is the last only on
    the loop's plumbing."""
    names, scopes, parts = walked_names[model]
    for name in names:
        found = _tokens(name, scopes + parts)
        if any(token in parts for token in found):
            part = next(i for i, token in enumerate(found)
                        if token in parts)
            assert COARSE[model] in found[:part], name
            assert _tokens(name, scopes)[-1] == COARSE[model], name
    walks = [name.rsplit("/", 1)[-1] for name in names
             if _tokens(name, scopes)[-1:] == ["peer_walk"]]
    assert "dot_general" not in walks
    assert {"dynamic_slice", "dynamic_update_slice", "while"} <= set(walks)
    # the products of the block are read under their parts (the tiny
    # preset's core is the `einsum` form: Laguna's has products too)
    products = {_tokens(name, scopes + parts)[-1] for name in names
                if name.endswith("dot_general")
                and COARSE[model] in _tokens(name, scopes)[-1:]}
    assert products - {"attn_core"} == {"attn_in", "attn_out"}


@pytest.mark.parametrize("model", sorted(COARSE))
def test_a_block_of_one_peer_walks_nothing(model):
    """`peer_at_a_time` hands a block of one peer straight to its
    function: no loop, no `peer_walk` in any `op_name` (Granite's cell
    runs such a block), and the parts are there all the same."""
    alone = Simulator(_cfg(**dict(LM_TINY, model_name=model)))
    alone.steps.block = 1
    names = re.findall(r'op_name="([^"]*)"', alone.round_hlo())
    assert not [name for name in names if _names(name, "peer_walk")]
    assert any(_names(name, "attn_in") for name in names)


def test_no_scope_is_part_of_another_or_a_frozen_leafs_name():
    """The join takes the LAST token of an `op_name`, and a parameter's
    `op_name` holds its path in the frozen tree (`attn_norm`, `q_norm`):
    over the round's stages, the three language models' scopes and their
    parts, no name is part of another and none is a leaf's key."""
    names, leaves = set(STAGES), set()

    def keys(tree):
        if isinstance(tree, dict):
            for key, below in tree.items():
                leaves.add(key)
                keys(below)
        elif isinstance(tree, list):
            for below in tree:
                keys(below)

    for module in (laguna, deepseek_v2, granite_hybrid):
        names |= set(module.SCOPES) | set(getattr(module, "SUBSCOPES", ()))
        for cfg in module.PRESETS.values():
            for tree in module._shapes(cfg):
                keys(tree)
    assert {"attn_norm", "q_norm", "wo", "lora_a"} <= leaves
    assert {"peer_walk", "attn_norms", "attn_core"} <= names
    assert not [(a, b) for a in names for b in names if a != b and a in b]
    assert not names & leaves
    assert not hasattr(granite_hybrid, "SUBSCOPES")  # its cell is untouched
