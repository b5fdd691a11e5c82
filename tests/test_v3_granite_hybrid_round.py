"""Granite-4.0-H-Micro's hybrid on the system's own path (the tiny
preset): the zoo and its datasets, the published sizes from shapes alone,
a block of peers against peer by peer, one `round_step` against the plain
reference's round, `Trainer`, `Simulator` and `HiveStepper` through the one
`Model` interface, and the round's gauges. The parity of the model with
the plain reference is tests/test_v3_granite_hybrid.py's (whose module doc
says why these two files are named to be collected last)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as ref
from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import deepseek_v2, granite_hybrid, laguna, lm
from biscotti_tpu.models.trainer import (Trainer, block_step_fn,
                                         local_step_fn)
from biscotti_tpu.models.zoo import MODELS, model_for_dataset
from biscotti_tpu.parallel.sim import Simulator

from test_v3_granite_hybrid import DATASET, NAME, TINY, published


@pytest.fixture(scope="module")
def tiny():
    model = model_for_dataset(DATASET, NAME)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def test_a_block_of_peers_is_each_peer_alone(tiny):
    """The peers' windows as ONE batch through the conv, the scan and the
    attention, the per-peer part confined to the adapters: every row of
    the block's deltas is that peer's own step."""
    model, frozen, w, x, y = tiny
    block = jax.jit(block_step_fn(model, "clipped_sgd", 0.005, 0.1))
    one = local_step_fn(model, "clipped_sgd", 0.005, 0.1)
    xb = jnp.asarray(x[:6]).reshape(3, 2, -1)
    yb = jnp.asarray(y[:6]).reshape(3, 2, -1)
    deltas, counts = block(w, xb, yb, frozen)
    assert deltas.shape == (3, model.num_params) and counts == {}
    for peer in range(3):
        np.testing.assert_allclose(deltas[peer],
                                   one(w, xb[peer], yb[peer], frozen),
                                   atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(deltas, axis=1), 0.1 * 0.005,
                               rtol=1e-4)  # every peer's step is clipped


# ------------------------------------------------- the system's own path


def _cfg(**kw):
    base = dict(dataset=DATASET, model_name=NAME, num_nodes=6, batch_size=8,
                epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
                num_miners=1, num_noisers=1, learning_rate=0.1,
                grad_clip=0.05, seed=9)
    return BiscottiConfig(**{**base, **kw})


def test_the_zoo_registers_both_presets_and_their_datasets():
    assert set(granite_hybrid.PRESETS) <= set(MODELS)
    model = model_for_dataset(DATASET, NAME)
    assert model.name == NAME and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    assert model.num_params == 1272
    assert model.info["ssm_chunks"] == 4
    assert model.info["attention"] == {"fused": 0, "block_share": 1.0}
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", NAME)
    with pytest.raises(ValueError, match="100352"):
        model_for_dataset(DATASET, "granite_h_micro_fedlora")
    spec = ds.spec("lm_tokens_granite")
    assert spec.tokens and spec.n_classes == 100352 and spec.d_in == 1024
    # the scopes are the model's own, and the others' stay theirs
    assert {"ssm_scan", "ssm_proj", "ssm_conv", "ssm_gate"} <= set(
        granite_hybrid.SCOPES)
    assert not {"ssm_scan", "ssm_proj"} & set(laguna.SCOPES
                                              + deepseek_v2.SCOPES)


def test_the_published_sizes_from_shapes_alone():
    """What the dataset trains where no model is named: the WHOLE model,
    d = 6,410,240 and 3,195,459,328 frozen parameters, the embedding
    counted once; no parameter is drawn to learn it."""
    big = model_for_dataset("lm_tokens_granite")
    cfg = big.info["config"]
    assert big.name == "granite_h_micro_fedlora"
    assert cfg.layers == 40 and cfg.layer_types.count("mamba") == 36
    assert [at for at, kind in enumerate(cfg.layer_types)
            if kind == "attention"] == [5, 15, 25, 35]
    assert big.num_params == 36 * 16 * (8512 + 2048) + 4 * 16 * 5120 \
        == 6410240
    assert lm.frozen_count(big) == 3195459328
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))
    assert "head" not in shapes and shapes["embed"].shape == (100352, 2048)
    assert shapes["layers"][0]["w_in"].shape == (2048, 8512)
    assert shapes["layers"][5]["wk"].shape == (2048, 512)
    assert big.info["ssm_chunks"] == 4
    assert big.step_bytes(2) == 2 * big.step_bytes(1)


def test_one_round_step_is_the_references_round():
    """`Simulator.round_step` from seeded adapters against
    benchmark/reference/granite_hybrid.py's round: the same sampled peers,
    windows and noise (re-derived through reference/round.py), the
    token-by-token state-space layers, Krum's oracle, the sum, the
    ledger."""
    cfg = _cfg(num_nodes=8, batch_size=2, sample_percent=0.7, grad_clip=1.0)
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
    want = ref.reference_round(
        published(TINY), rnd, cfg.seed, 0, w_in, stake_in, sim.frozen,
        shard_rows, test["x_test"], test["y_test"], jnp.float64)
    assert cfg.num_samples == 5 and int(np.sum(mask)) == 3
    np.testing.assert_array_equal(np.asarray(mask), want["accept"])
    np.testing.assert_array_equal(np.asarray(stake_next),
                                  want["stake_next"])
    update = np.asarray(w_next, np.float64) - w_in
    assert np.linalg.norm(want["agg"]) > 0
    np.testing.assert_allclose(update, want["agg"], atol=1e-5 * np.abs(
        want["agg"]).max())
    assert float(err) == pytest.approx(want["err"], abs=0.04)


def test_trainer_step_is_the_simulators_for_the_same_batch():
    cfg = _cfg()
    sim = Simulator(cfg)
    assert sim.mode == "clipped_sgd" and sim.rows == 8
    assert sim.model.name == NAME and sim.last_counts == {}
    w = sim.model.flat_init(jax.random.PRNGKey(4))
    cidx, deltas, _ = sim._noised_jit(
        w, 0, jnp.asarray(cfg.seed, jnp.int32), sim.x, sim.y, sim.frozen)
    trainer = Trainer(DATASET, f"{DATASET}3", cfg=cfg)
    assert trainer.model.name == NAME
    mine = trainer.private_fun(np.asarray(w), 0)
    row = int(np.nonzero(np.asarray(cidx) == 3)[0][0])
    np.testing.assert_allclose(mine, deltas[row], atol=1e-7)
    assert trainer.test_error(np.asarray(w)) == pytest.approx(
        sim.test_error(w))


def test_the_round_trains_the_adapters_and_reports_its_chunks():
    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    sim = Simulator(_cfg(batch_size=2), metrics=registry)
    w, stake, logs = sim.run(num_rounds=2, stop_at_convergence=False)
    assert w.shape == (1272,) and np.isfinite(w).all() and np.asarray(w).any()
    assert logs[-1].accepted == 4 - 4 // 2
    page = registry.render()
    for name in ("biscotti_sim_frozen_bytes", "biscotti_sim_peer_block",
                 "biscotti_lm_attention_fused 0",
                 "biscotti_lm_attention_shared_key 0",
                 "biscotti_ssm_chunks 4"):
        assert name in page, name
    assert "biscotti_moe_" not in page  # no router, nothing dispatched
    assert sim.dispatch_stats() == {}
    # the other language models state no chunks
    other = MetricsRegistry()
    Simulator(_cfg(model_name="laguna_tiny", batch_size=2),
              metrics=other).run(num_rounds=1, stop_at_convergence=False)
    assert "biscotti_ssm_chunks" not in other.render()


def test_the_walked_peer_axis_gives_the_same_deltas():
    """`peer_block` peers at a time (`lax.map` over blocks of one program)
    or all at once: the same rows."""
    cfg = _cfg(batch_size=2)
    sim = Simulator(cfg)
    w = sim.model.flat_init(jax.random.PRNGKey(4))
    seed = jnp.asarray(cfg.seed, jnp.int32)
    _, whole, _ = sim._noised_jit(w, 0, seed, sim.x, sim.y, sim.frozen)
    sim.steps.block = 2
    jax.clear_caches()
    _, walked, _ = jax.jit(sim._build_round_step()[1])(
        w, 0, seed, sim.x, sim.y, sim.frozen)
    np.testing.assert_allclose(walked, whole, atol=1e-7)


def test_the_hive_stepper_steps_the_model_as_the_trainer_does():
    """`HiveStepper` through the same `Model` interface: one batched
    dispatch whose rows are each co-hosted peer's own Trainer's delta."""
    import asyncio

    from biscotti_tpu.runtime.hive import HiveStepper

    n = 3
    cfg = _cfg(num_nodes=n, batch_size=2, grad_clip=1.0, noising=False,
               verification=False, base_port=13930, seed=3)
    stepper = HiveStepper(cfg, range(n))
    assert stepper.num_params == 1272
    w = np.asarray(model_for_dataset(DATASET, NAME).flat_init(
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
                                   rtol=1e-5, atol=1e-6)
