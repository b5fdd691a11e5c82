"""The round-level tests every language model of the family runs (the tiny
preset on the system's own path: the zoo, a block of peers against peer by
peer, one `round_step` against the plain reference's round, `Trainer`,
`Simulator` and `HiveStepper` through the one `Model` interface, the round's
gauges), written ONCE over a record of what differs between models.

pytest does not collect this file. A model's file (tests/test_laguna.py,
tests/test_v{2,3,4,5}_*_round.py) states its `FAMILY`, imports the cases it
runs (`from lm_family import family, tiny, test_...`: an imported test is
collected, run and counted in the file that imports it) and keeps the tests
only it has. The files stay apart on purpose: `--dist loadfile` spreads
files over the workers, not tests, and one file of five models' rounds
would be the longest of the run. A case a model's file wraps (`a_block_...`,
`the_round_trains_...`: no `test_` prefix) hands back what it built, for the
assertions only that model makes.
"""

import asyncio
import re
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import lm
from biscotti_tpu.models.peer_step import (BLOCK_SHARE, DEVICE_BYTES,
                                           peer_block)
from biscotti_tpu.models.trainer import (Trainer, block_step_fn,
                                         local_step_fn)
from biscotti_tpu.models.zoo import MODELS, model_for_dataset
from biscotti_tpu.parallel.sim import Simulator

DATASET = "lm_tokens_tiny"


@dataclass(frozen=True)
class Family:
    """What differs between two models' copies of a case."""
    module: Any                  # biscotti_tpu/models/<model>.py
    ref: Any                     # benchmark/reference/<model>.py
    name: str                    # the tiny preset
    published: Callable          # the preset in config.json's keys
    num_params: int              # the tiny preset's d
    # counts["load"]'s [sparse layers, held experts]; None: no router
    load: Optional[Tuple[int, int]]
    gauges: Tuple[str, ...]      # lines of the page after two rounds
    no_gauges: Tuple[str, ...] = ()   # and what is not on it
    # (a sibling's preset, gauges ITS page must not show after a round)
    sibling: Tuple[str, Tuple[str, ...]] = ("laguna_tiny", ())
    clip: float = 0.005          # of `a_block_of_peers_is_each_peer_alone`
    port: int = 0                # the hive stepper's base port
    round_atol: float = 2e-5     # of the round's update, x the largest entry
    stepper_atol: float = 1e-6   # of the hive stepper's rows, absolute
    # the published preset: (dataset, name, vocabulary held, d, frozen
    # parameters)
    big: Tuple[str, str, int, int, int] = None
    # scopes the compiled round's walk holds, scopes it does not, and names
    # the round's text holds nowhere
    walked: Tuple[str, ...] = ()
    not_walked: Tuple[str, ...] = ()
    not_in_round: Tuple[str, ...] = ()
    # `the_peer_block_is_...`: a peer's step bytes (lo, hi), the free bytes
    # (lo, hi), three peers' share of them (lo, hi), {x the free bytes:
    # the block `peer_block` takes}
    block_rule: Tuple = None

    @property
    def tiny(self):
        return self.module.PRESETS[self.name]


@pytest.fixture(scope="module")
def family(request) -> Family:
    return request.module.FAMILY


@pytest.fixture(scope="module")
def tiny(family):
    model = model_for_dataset(DATASET, family.name)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def cfg_of(name, **kw):
    """The configuration the family's rounds run a tiny preset under."""
    base = dict(dataset=DATASET, model_name=name, num_nodes=6,
                batch_size=8, epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
                num_miners=1, num_noisers=1, learning_rate=0.1,
                grad_clip=0.05, seed=9)
    return BiscottiConfig(**{**base, **kw})


# ------------------------------------------------- a block and its peers


def a_block_of_peers_is_each_peer_alone(family, built):
    """The peers' tokens as ONE batch through the model (one dispatch over
    the block's tokens where there is a router, each peer's attention
    walked alone inside the block), the per-peer part confined to the
    adapters: every row of the block's deltas is that peer's own step, and
    the dispatch counts every assignment held. `built`: (model, frozen, w,
    x, y); gives the block's (tokens, counts)."""
    model, frozen, w, x, y = built
    block = jax.jit(block_step_fn(model, "clipped_sgd", family.clip, 0.1))
    one = local_step_fn(model, "clipped_sgd", family.clip, 0.1)
    xb = jnp.asarray(x[:6]).reshape(3, 2, -1)
    yb = jnp.asarray(y[:6]).reshape(3, 2, -1)
    deltas, counts = block(w, xb, yb, frozen)
    assert deltas.shape == (3, model.num_params)
    for peer in range(3):
        np.testing.assert_allclose(deltas[peer],
                                   one(w, xb[peer], yb[peer], frozen),
                                   atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(deltas, axis=1),
                               0.1 * family.clip,
                               rtol=1e-4)  # every peer's step is clipped
    if family.load is None:
        assert counts == {}
    else:
        assert counts["load"].shape == family.load
        assert int(counts["dropped"].sum()) == 0
    return xb, counts


def test_a_block_of_peers_is_each_peer_alone(family, tiny):
    a_block_of_peers_is_each_peer_alone(family, tiny)


def walked_names(hlo):
    """The `op_name`s of a compiled program's text that lie under
    `lm.peer_at_a_time`'s loop."""
    return [name for name in re.findall(r'op_name="([^"]*)"', hlo)
            if "peer_walk" in name]


def test_the_attention_is_walked_and_every_scope_is_in_the_round(family):
    """A block's attention layers run their mixer under
    `lm.peer_at_a_time`, what the model keeps out of the walk is out of
    it, and every scope and part the model declares is in the compiled
    round."""
    sim = Simulator(cfg_of(family.name, batch_size=2))
    hlo = sim.round_hlo()
    for scope in family.module.SCOPES:
        assert scope in hlo, scope
    for part in family.module.SUBSCOPES:
        assert f"lm_attention/{part}" in hlo, part
    assert sim.peer_block > 1
    walked = walked_names(hlo)
    for scope in family.walked:
        assert any(scope in name for name in walked), scope
    for scope in family.not_walked:
        assert not any(scope in name for name in walked), scope
    for name in family.not_in_round:
        assert name not in hlo, name


# ------------------------------------------------- the system's own path


def test_the_zoo_registers_both_presets_and_their_datasets(family):
    """The tiny preset by name and the published one as its dataset's
    default, the step rule declared, and a dataset that is no token ids, or
    another vocabulary's, refused."""
    dataset, name, vocab = family.big[:3]
    assert {family.name, name} <= set(family.module.PRESETS) <= set(MODELS)
    model = model_for_dataset(DATASET, family.name)
    assert model.name == family.name and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    assert model.num_params == family.num_params
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", family.name)
    with pytest.raises(ValueError, match=str(vocab)):
        model_for_dataset(DATASET, name)
    spec = ds.spec(dataset)
    big = model_for_dataset(dataset)
    assert big.name == name
    assert spec.tokens and (spec.n_classes, spec.d_in) == (vocab, big.d_in)


def test_the_published_sizes_from_shapes_alone(family):
    """What the dataset trains where no model is named: d and the frozen
    parameters of the published preset; no parameter is drawn to learn it
    (the model's own file counts them part by part)."""
    dataset, name, vocab, d, frozen = family.big
    big = model_for_dataset(dataset)
    assert big.name == name and big.n_classes == vocab
    assert big.num_params == d
    assert lm.frozen_count(big) == frozen
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}
    assert big.step_bytes(2) == 2 * big.step_bytes(1)


def test_one_round_step_is_the_references_round(family):
    """`Simulator.round_step` from seeded adapters against the plain
    reference's round (benchmark/reference/<model>.py): the same sampled
    peers, windows and noise (re-derived through reference/round.py), the
    reference's own forward, Krum's oracle, the sum, the ledger."""
    cfg = cfg_of(family.name, num_nodes=8, batch_size=2, sample_percent=0.7,
                 grad_clip=1.0)
    sim = Simulator(cfg)
    w = sim.model.flat_init(jax.random.PRNGKey(11))
    _, stake = sim.init_state()
    w_in, stake_in = np.asarray(w), np.asarray(stake)
    w_next, stake_next, mask, err = sim.round_step(w, stake, 0)
    rnd = {"n": cfg.num_nodes, "s": cfg.num_samples, "rows": sim.rows,
           "batch": cfg.batch_size, "clip": cfg.grad_clip,
           "eta": cfg.learning_rate, "epsilon": cfg.epsilon,
           "delta": cfg.delta, "noising": cfg.noising,
           "verification": cfg.verification, "stake_unit": cfg.stake_unit}

    def shard_rows(peer, idx):
        shard = ds.load_shard(DATASET, f"{DATASET}{peer}")
        return shard["x_train"][idx], shard["y_train"][idx]

    test = ds.load_shard(DATASET, f"{DATASET}_test")
    want = family.ref.reference_round(
        family.published(family.tiny), rnd, cfg.seed, 0, w_in, stake_in,
        sim.frozen, shard_rows, test["x_test"], test["y_test"], jnp.float64)
    assert cfg.num_samples == 5 and int(np.sum(mask)) == 3
    np.testing.assert_array_equal(np.asarray(mask), want["accept"])
    np.testing.assert_array_equal(np.asarray(stake_next),
                                  want["stake_next"])
    update = np.asarray(w_next, np.float64) - w_in
    assert np.linalg.norm(want["agg"]) > 0
    np.testing.assert_allclose(
        update, want["agg"],
        atol=family.round_atol * np.abs(want["agg"]).max())
    assert float(err) == pytest.approx(want["err"], abs=0.04)


def test_trainer_step_is_the_simulators_for_the_same_batch(family):
    """A batch of all 8 windows of a shard: whatever order each side draws
    them in, the mean loss is the same, so peer 3's delta from its own
    Trainer is the row the round computes for it; and the noise is scaled
    by the same eta as the step."""
    cfg = cfg_of(family.name)
    sim = Simulator(cfg)
    assert sim.mode == "clipped_sgd" and sim.rows == 8
    assert sim.model.name == family.name
    if family.load is None:
        assert sim.last_counts == {}
    w = sim.model.flat_init(jax.random.PRNGKey(4))
    cidx, deltas, noised = sim._noised_jit(
        w, 0, jnp.asarray(cfg.seed, jnp.int32), sim.x, sim.y, sim.frozen)
    trainer = Trainer(DATASET, f"{DATASET}3", cfg=cfg)
    assert trainer.model.name == family.name
    assert trainer.mode == "clipped_sgd"
    mine = trainer.private_fun(np.asarray(w), 0)
    row = int(np.nonzero(np.asarray(cidx) == 3)[0][0])
    np.testing.assert_allclose(mine, deltas[row], atol=1e-7)
    spread = float(jnp.std(noised - deltas))
    sigma = np.sqrt(2 * np.log(1.25 / cfg.delta)) / cfg.epsilon
    np.testing.assert_allclose(spread, 0.1 * sigma / np.sqrt(8), rtol=0.1)
    np.testing.assert_allclose(np.std(trainer.get_noise(0)), spread, rtol=0.2)
    assert trainer.test_error(np.asarray(w)) == pytest.approx(
        sim.test_error(w))


def the_round_trains_the_adapters_and_reports(family):
    """Two rounds with a registry: the adapters move, Krum accepts two of
    four, and the page holds the model's lines and none of `no_gauges`; a
    sibling's page shows none of what only this model declares. Gives the
    simulator."""
    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    sim = Simulator(cfg_of(family.name, batch_size=2), metrics=registry)
    w, stake, logs = sim.run(num_rounds=2, stop_at_convergence=False)
    assert w.shape == (family.num_params,)
    assert np.isfinite(w).all() and np.asarray(w).any()
    assert logs[-1].accepted == 4 - 4 // 2
    page = registry.render()
    for line in ("biscotti_sim_frozen_bytes",
                 "biscotti_sim_peer_block") + family.gauges:
        assert line in page, line
    for line in family.no_gauges:
        assert line not in page, line
    stats = sim.dispatch_stats()
    if family.load is None:
        assert stats == {} and "biscotti_moe_" not in page
    else:
        assert stats["tokens_dropped"] == 0
        assert stats["assignments_held"] > 0
        assert stats["load_max_over_mean"] >= 1.0
    name, absent = family.sibling
    if absent:
        other = MetricsRegistry()
        Simulator(cfg_of(name, batch_size=2),
                  metrics=other).run(num_rounds=1, stop_at_convergence=False)
        for line in absent:
            assert line not in other.render(), line
    return sim


def test_the_walked_peer_axis_gives_the_same_deltas(family):
    """`peer_block` peers at a time (`lax.map` over blocks of one program)
    or all at once: the same rows."""
    cfg = cfg_of(family.name, batch_size=2)
    sim = Simulator(cfg)
    w = sim.model.flat_init(jax.random.PRNGKey(4))
    seed = jnp.asarray(cfg.seed, jnp.int32)
    _, whole, _ = sim._noised_jit(w, 0, seed, sim.x, sim.y, sim.frozen)
    sim.steps.block = 2
    jax.clear_caches()
    _, walked, _ = jax.jit(sim._build_round_step()[1])(
        w, 0, seed, sim.x, sim.y, sim.frozen)
    np.testing.assert_allclose(walked, whole, atol=1e-7)


def test_the_peer_block_is_what_the_step_bytes_leave_room_for(family):
    """The published preset's `step_bytes` against what the chip's runtime
    states less the standing arrays (the base in bfloat16, 30 peers'
    stacks of 64 windows, the deltas and noise of 21 sampled peers): the
    block `peer_step.peer_block` takes there, and with more and less
    room."""
    dataset, _, _, d, frozen = family.big
    step_range, free_range, share_range, blocks = family.block_rule
    big = model_for_dataset(dataset)
    step = big.step_bytes(1)
    free = DEVICE_BYTES - (2 * frozen + 30 * 2 * 64 * big.d_in * 4
                           + 4 * (3 * 21 + 2) * d)
    assert step_range[0] < step < step_range[1]
    assert free_range[0] < free < free_range[1]
    assert share_range[0] < 3 * step / free - BLOCK_SHARE < share_range[1]
    for room, block in blocks.items():
        assert peer_block(21, step, int(room * free)) == block, room


def test_the_hive_stepper_steps_the_model_as_the_trainer_does(family):
    """`HiveStepper` through the same `Model` interface: one batched
    dispatch whose rows are each co-hosted peer's own Trainer's delta."""
    from biscotti_tpu.runtime.hive import HiveStepper

    n = 3
    cfg = cfg_of(family.name, num_nodes=n, batch_size=2, grad_clip=1.0,
                 noising=False, verification=False, base_port=family.port,
                 seed=3)
    stepper = HiveStepper(cfg, range(n))
    assert stepper.num_params == family.num_params
    w = np.asarray(model_for_dataset(DATASET, family.name).flat_init(
        jax.random.PRNGKey(1)), np.float64)

    async def go():
        return await asyncio.gather(*(stepper.step(pid, w, 0)
                                      for pid in range(n)))

    outs = asyncio.run(go())
    assert stepper.batches == 1
    for pid in range(n):
        trainer = Trainer(DATASET, ds.shard_name(DATASET, pid, False),
                          cfg=cfg, seed=pid)
        assert np.any(outs[pid])
        np.testing.assert_allclose(outs[pid], trainer.private_fun(w, 0),
                                   rtol=1e-5, atol=family.stepper_atol)
