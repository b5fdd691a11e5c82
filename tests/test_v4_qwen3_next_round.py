"""Qwen3-Next-80B-A3B-Instruct's hybrid on the system's own path (the tiny
preset): the zoo and its datasets, the published sizes from shapes alone,
a block of peers against peer by peer, one `round_step` against the plain
reference's round, `Trainer`, `Simulator` and `HiveStepper` through the one
`Model` interface, and the round's gauges. The parity of the model with
the plain reference is tests/test_v4_qwen3_next.py's (whose module doc says
why these two files are named to be collected last)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import (deepseek_v2, granite_hybrid, laguna, lm,
                                 qwen3_next)
from biscotti_tpu.models.peer_step import (BLOCK_SHARE, DEVICE_BYTES,
                                           peer_block)
from biscotti_tpu.models.trainer import (Trainer, block_step_fn,
                                         local_step_fn)
from biscotti_tpu.models.zoo import DEFAULTS, MODELS, model_for_dataset
from biscotti_tpu.parallel.sim import Simulator

from test_v4_qwen3_next import DATASET, NAME, TINY, published


@pytest.fixture(scope="module")
def tiny():
    model = model_for_dataset(DATASET, NAME)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def test_a_block_of_peers_is_each_peer_alone(tiny):
    """The peers' tokens as ONE batch through the router and the grouped
    products, each peer's mixer walked alone inside the block, the per-peer
    part confined to the adapters: every row of the block's deltas is that
    peer's own step, and the dispatch counts every assignment held."""
    model, frozen, w, x, y = tiny
    block = jax.jit(block_step_fn(model, "clipped_sgd", 0.005, 0.1))
    one = local_step_fn(model, "clipped_sgd", 0.005, 0.1)
    xb = jnp.asarray(x[:6]).reshape(3, 2, -1)
    yb = jnp.asarray(y[:6]).reshape(3, 2, -1)
    deltas, counts = block(w, xb, yb, frozen)
    assert deltas.shape == (3, model.num_params)
    assert counts["load"].shape == (8, 4) and int(counts["dropped"].sum()) == 0
    for peer in range(3):
        np.testing.assert_allclose(deltas[peer],
                                   one(w, xb[peer], yb[peer], frozen),
                                   atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(deltas, axis=1), 0.1 * 0.005,
                               rtol=1e-4)  # every peer's step is clipped


def test_the_attention_is_walked_and_every_scope_is_in_the_round():
    """A block's attention layers run their mixer under
    `lm.peer_at_a_time` (the delta net the block's windows as one batch),
    and every scope and part the model declares is in the compiled
    round."""
    sim = Simulator(_cfg(batch_size=2))
    hlo = sim.round_hlo()
    for scope in qwen3_next.SCOPES:
        assert scope in hlo, scope
    for part in qwen3_next.SUBSCOPES:
        assert f"lm_attention/{part}" in hlo, part
    assert sim.peer_block > 1
    import re

    walked = [name for name in re.findall(r'op_name="([^"]*)"', hlo)
              if "peer_walk" in name]
    assert any("attn_core" in name for name in walked)
    assert not any("gdn_" in name for name in walked)


# ------------------------------------------------- the system's own path


def _cfg(**kw):
    base = dict(dataset=DATASET, model_name=NAME, num_nodes=6, batch_size=8,
                epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
                num_miners=1, num_noisers=1, learning_rate=0.1,
                grad_clip=0.05, seed=9)
    return BiscottiConfig(**{**base, **kw})


def test_the_zoo_registers_both_presets_and_their_datasets():
    assert set(qwen3_next.PRESETS) <= set(MODELS)
    assert DEFAULTS["lm_tokens_qwen3next"] == "qwen3_next_fedlora"
    model = model_for_dataset(DATASET, NAME)
    assert model.name == NAME and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    assert model.num_params == 6 * 2 * (96 + 32) + 2 * 2 * (64 + 16 + 16 + 32)
    assert model.info["gdn_chunks"] == 4
    assert model.info["attention"] == {"fused": 0, "block_share": 1.0}
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", NAME)
    with pytest.raises(ValueError, match="37984"):
        model_for_dataset(DATASET, "qwen3_next_fedlora")
    spec = ds.spec("lm_tokens_qwen3next")
    assert spec.tokens and spec.n_classes == 37984 and spec.d_in == 1024
    # the scopes are the model's own, and the others' stay theirs
    assert {"gdn_rule", "gdn_proj", "gdn_conv", "gdn_gate", "peer_walk"} \
        <= set(qwen3_next.SCOPES)
    assert not {"gdn_rule", "gdn_proj"} & set(
        laguna.SCOPES + deepseek_v2.SCOPES + granite_hybrid.SCOPES)
    assert qwen3_next.SUBSCOPES == laguna.SUBSCOPES


def test_the_published_sizes_from_shapes_alone():
    """What the dataset trains where no model is named: three whole
    periods at the published widths, 5,424,460,992 frozen parameters
    (10.85 GB in bfloat16) and d = 2,605,056; no parameter is drawn to
    learn it. ISSUE 38's table, part by part."""
    big = model_for_dataset("lm_tokens_qwen3next")
    cfg = big.info["config"]
    assert big.name == "qwen3_next_fedlora" and cfg.layers == 12
    assert cfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 3
    assert big.num_params == 9 * 16 * (12288 + 2048) \
        + 3 * 16 * (8192 + 512 + 512 + 2048) == 2605056
    assert lm.frozen_count(big) == 5424460992
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    sparse = ("mlp_norm", "router", "shared", "shared_gate", "experts")
    delta, full = shapes["layers"][0], shapes["layers"][3]
    mixer = [name for name in delta if name not in sparse + ("lora_a",)]
    assert count({n: delta[n] for n in mixer}) == 33718464 + 2048
    assert count({n: full[n] for n in full
                  if n not in sparse + ("lora_a",)}) == 27263488 + 2048
    assert count({n: delta[n] for n in sparse}) == 406853632 - 2048
    assert count(delta) - count(delta["lora_a"]) == 440572096
    assert count(full) - count(full["lora_a"]) == 434117120
    assert count([layer["lora_a"] for layer in shapes["layers"]]) == 1376256
    assert shapes["embed"].shape == (37984, 2048)
    assert shapes["head"].shape == (2048, 37984)
    assert delta["w_qkvz"].shape == (2048, 12288)
    assert delta["w_ba"].shape == (2048, 64)
    assert delta["conv_w"].shape == (4, 8192) and "conv_b" not in delta
    assert delta["experts"]["w_gate"].shape == (128, 2048, 512)
    assert delta["router"].shape == (2048, 512)
    assert full["wq"].shape == (2048, 8192) and full["wk"].shape == (2048, 512)
    assert big.info["gdn_chunks"] == 16
    # the core finds a block at heads of 256 with eight query heads a
    # key/value head (the widest so far), and the experts' kernel a tile
    assert big.info["attention"] == {"fused": 1, "block_share": 0.5625}
    assert big.step_bytes(2) == 2 * big.step_bytes(1)


def test_one_round_step_is_the_references_round():
    """`Simulator.round_step` from seeded adapters against
    benchmark/reference/qwen3_next.py's round: the same sampled peers,
    windows and noise (re-derived through reference/round.py), the
    token-by-token delta rule, Krum's oracle, the sum, the ledger."""
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
    np.testing.assert_allclose(update, want["agg"], atol=2e-5 * np.abs(
        want["agg"]).max())
    assert float(err) == pytest.approx(want["err"], abs=0.04)


def test_trainer_step_is_the_simulators_for_the_same_batch():
    cfg = _cfg()
    sim = Simulator(cfg)
    assert sim.mode == "clipped_sgd" and sim.rows == 8
    assert sim.model.name == NAME
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
    assert w.shape == (2048,) and np.isfinite(w).all() and np.asarray(w).any()
    assert logs[-1].accepted == 4 - 4 // 2
    page = registry.render()
    for name in ("biscotti_sim_frozen_bytes", "biscotti_sim_peer_block",
                 "biscotti_lm_attention_fused 0",
                 "biscotti_lm_attention_shared_key 0",
                 "biscotti_gdn_chunks 4", "biscotti_gdn_rule_kernel 0",
                 "biscotti_moe_tokens_dropped 0",
                 "biscotti_moe_tile_fill", "biscotti_moe_grouped_kernel 0"):
        assert name in page, name
    assert "biscotti_ssm_chunks" not in page
    stats = sim.dispatch_stats()
    assert stats["tokens_dropped"] == 0 and stats["assignments_held"] > 0
    # the other hybrid states no chunks of the rule
    other = MetricsRegistry()
    Simulator(_cfg(model_name="granite_h_tiny", batch_size=2),
              metrics=other).run(num_rounds=1, stop_at_convergence=False)
    assert "biscotti_gdn_chunks" not in other.render()
    assert "biscotti_gdn_rule_kernel" not in other.render()


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


def test_the_peer_block_is_what_the_step_bytes_leave_room_for():
    """The published preset's `step_bytes` (0.970 GB: read off the compiled
    round's memory analysis, PERF.md section 6, PR 39: the rule a kernel,
    a peer adds 0.99 GB where 1.13 while it was `jax.numpy`) against what
    the chip's runtime states less the standing arrays: three peers are
    0.542 of the free bytes, inside `BLOCK_SHARE`, so the cell walks
    THREE at a time. A tenth less free memory and the rule would take 1;
    seven are far out."""
    big = model_for_dataset("lm_tokens_qwen3next")
    step = big.step_bytes(1)
    free = DEVICE_BYTES - (2 * 5424460992 + 30 * 2 * 64 * 1024 * 4
                           + 4 * (3 * 21 + 2) * 2605056)
    assert 0.96e9 < step < 1.0e9
    assert BLOCK_SHARE - 0.07 < 3 * step / free < BLOCK_SHARE
    assert peer_block(21, step, free) == peer_block(21, step,
                                                    int(2 * free)) == 3
    assert peer_block(21, step, int(0.9 * free)) == 1


def test_the_hive_stepper_steps_the_model_as_the_trainer_does():
    """`HiveStepper` through the same `Model` interface: one batched
    dispatch whose rows are each co-hosted peer's own Trainer's delta."""
    import asyncio

    from biscotti_tpu.runtime.hive import HiveStepper

    n = 3
    cfg = _cfg(num_nodes=n, batch_size=2, grad_clip=1.0, noising=False,
               verification=False, base_port=13960, seed=3)
    stepper = HiveStepper(cfg, range(n))
    assert stepper.num_params == 2048
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
