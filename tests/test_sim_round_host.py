"""The host side of `Simulator.round_step` (PERF.md section 6, PR 44): the
seed stands on the device, `_at_home` is asked about what enters from
outside only, the round counter is staged behind the running round, and
the program behind all of it is the one the parent ran. The stack is
COMMITTED here, as `put_stack` leaves it on the chip (on the CPU it is not:
nothing would be placed and nothing could compile twice).
CPU: what is asserted is calls, counts and values, never a time."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.parallel import sim as sim_module
from biscotti_tpu.parallel.sim import Simulator
from biscotti_tpu.telemetry import MetricsRegistry

CONFIGS = {
    "softmax": dict(dataset="mnist", model_name="softmax", num_nodes=10,
                    sample_percent=0.6, batch_size=10),
    # a frozen tree: hundreds of leaves at the published size, a few here
    "lm_tokens_tiny": dict(dataset="lm_tokens_tiny", num_nodes=8,
                           sample_percent=1.0, batch_size=2,
                           learning_rate=0.1, grad_clip=1.0),
}


@pytest.fixture(params=sorted(CONFIGS))
def build(request, monkeypatch):
    """`build()` makes a Simulator of the parametrised model whose stack is
    committed to its device."""
    monkeypatch.setattr(
        sim_module, "put_stack",
        lambda a, sharding=None: jax.device_put(a, jax.devices()[0]))

    def build(metrics=None):
        cfg = BiscottiConfig(num_verifiers=1, num_miners=1, epsilon=1.0,
                             noising=True, verification=True,
                             defense=Defense.KRUM, seed=3,
                             **CONFIGS[request.param])
        sim = Simulator(cfg, metrics=metrics)
        assert sim.x.committed
        assert (sim.frozen != {}) == (request.param == "lm_tokens_tiny")
        return sim

    return build


def _fresh(sim, seed):
    """Weights a caller brings from outside: on no device in particular."""
    draw = np.random.default_rng(seed).normal(size=sim.num_params)
    return jnp.asarray(0.01 * draw.astype(np.float32))


def _as_the_parent(sim, w, stake, it):
    """Round `it` through the jitted program itself, every argument built
    on the spot as the closure built them before PR 44: the seed through
    `jnp.asarray`, `it` the Python int."""
    w, stake = sim._at_home(w, stake)
    return sim._round_step_jit(
        w, stake, it, jnp.asarray(sim.cfg.seed, jnp.int32), sim.x, sim.y,
        sim.x_val, sim.y_val, sim.frozen)[:4]


def _same(got, want):
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_a_closed_loop_builds_nothing_on_the_way_to_the_call(build,
                                                             monkeypatch):
    sim = build()
    events = []

    def logged(name, fn):
        def call(*args, **kw):
            events.append(name)
            return fn(*args, **kw)
        return call

    w, stake = sim.init_state()
    assert sim.round_host_stats() == {"args_placed_total": 2,
                                      "round_counter_staged_share": 0.0}
    w, stake, _, _ = sim.round_step(w, stake, 0)  # compiles; `it` built
    monkeypatch.setattr(sim, "_place", logged("place", sim._place))
    monkeypatch.setattr(sim, "_at_home", logged("at_home", sim._at_home))
    monkeypatch.setattr(jax, "device_put",
                        logged("device_put", jax.device_put))
    monkeypatch.setattr(jnp, "asarray", logged("asarray", jnp.asarray))
    monkeypatch.setattr(sim, "_round_step_jit",
                        logged("dispatch", sim._round_step_jit))
    for it in range(1, 6):
        del events[:]
        w, stake, _, _ = sim.round_step(w, stake, it)
        # nothing before the call; behind it the next round's counter, one
        # host copy
        assert events == ["dispatch", "place", "device_put"], f"round {it}"
    stats = sim.round_host_stats()
    assert stats["args_placed_total"] == 2  # flat since init_state
    assert stats["round_counter_staged_share"] == 5 / 6
    for span in ("args", "dispatch", "stage"):  # the readers' two stay
        assert sim.phases.counts[f"sim.round.{span}"] == 6


def test_six_rounds_equal_the_parents_bit_for_bit(build):
    sim = build()
    w, stake = sim.init_state()
    w_ref, stake_ref = sim.init_state()
    for it in range(6):
        got = sim.round_step(w, stake, it)
        want = _as_the_parent(sim, w_ref, stake_ref, it)
        _same(got, want)
        (w, stake), (w_ref, stake_ref) = got[:2], want[:2]


def test_a_replayed_round_counter_gives_that_rounds_results(build):
    sim = build()
    w, stake = sim.init_state()
    w_ref, stake_ref = sim.init_state()
    for it in (0, 1, 0, 5, 6):
        got = sim.round_step(w, stake, it)
        want = _as_the_parent(sim, w_ref, stake_ref, it)
        _same(got, want)
        (w, stake), (w_ref, stake_ref) = got[:2], want[:2]
    # 1 follows 0 and 6 follows 5: those two took the staged counter
    assert sim.round_host_stats()["round_counter_staged_share"] == 2 / 5
    # a numpy integer is the same round, and the same program
    got = sim.round_step(w, stake, np.int64(7))
    _same(got, _as_the_parent(sim, w_ref, stake_ref, 7))


def test_every_mix_of_fresh_and_returned_arguments_is_one_program(build):
    sim = build()
    _, stake = sim.init_state()
    placed = sim.round_host_stats()["args_placed_total"]
    # everything fresh; fresh weights with a returned stake (the
    # benchmark's checked rounds); a round on its own results
    w, stake, _, _ = sim.round_step(_fresh(sim, 0), stake, 0)
    _, stake, _, _ = sim.round_step(_fresh(sim, 1), stake, 1)
    w, stake, _, _ = sim.round_step(_fresh(sim, 2), stake, 2)
    w, stake, _, _ = sim.round_step(w, stake, 3)
    # a caller's own committed arrays, never seen by the closure
    w, stake = (jax.device_put(np.asarray(a), sim.x.sharding)
                for a in (w, stake))
    w, stake, _, _ = sim.round_step(w, stake, 4)
    assert sim._round_step_jit._cache_size() == 1
    assert sim.round_host_stats()["args_placed_total"] == placed + 3


def test_round_hlo_lowers_what_the_round_runs(build, monkeypatch):
    """The trace's instruction names are joined to `round_hlo()`'s: it has
    to lower the entry parameters of the timed call, type for type."""
    sim = build()
    seen = []
    jitted = sim._round_step_jit
    monkeypatch.setattr(sim, "_round_step_jit",
                        lambda *args: seen.append(args) or jitted(*args))
    w, stake = sim.init_state()
    for it in range(2):  # `it` built on the spot, then the staged one
        w, stake, _, _ = sim.round_step(w, stake, it)

    def signature(lowered):
        avals = [(a.shape, a.dtype, a.weak_type)
                 for a in jax.tree.leaves(lowered.in_avals)]
        entry = re.search(r"func\.func public @main\((.*?)\)\s*->",
                          lowered.as_text(), re.S).group(1)
        return avals, re.findall(r"tensor<[^>]*>", entry)

    hlo = signature(jax.jit(sim._round_step_raw, donate_argnums=(0, 1))
                    .lower(*sim.round_arg_shapes()))
    assert ((), np.dtype("int32"), False) in hlo[0]
    for args in seen:
        ran = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.format)
                    if i < 2 else a for i, a in enumerate(args))  # donated
        assert signature(jitted.lower(*ran)) == hlo
        assert args[2].committed and args[3] is sim.seed


def test_run_sets_the_two_gauges(build):
    registry = MetricsRegistry()
    sim = build(metrics=registry)
    sim.run(4, stop_at_convergence=False)
    page = registry.render()
    assert re.search(r"^biscotti_sim_args_placed_total 2(\.0)?$", page, re.M)
    assert re.search(r"^biscotti_sim_round_counter_staged_share 0\.75$",
                     page, re.M)


def test_run_scan_takes_the_standing_seed(build):
    sim = build()
    _, _, errs, accepted = sim.run_scan(3)
    w, stake = sim.init_state()
    for it in range(3):
        w, stake, mask, err = sim.round_step(w, stake, it)
        assert float(err) == errs[it] and int(mask.sum()) == accepted[it]
    sim.run_scan(3, seed=5)  # another seed: an argument, the same program
    assert sim._scan_cache[3]._cache_size() == 1
