"""MiMo-V2.5's decoder on the system's own path (the tiny preset): the zoo
and its datasets (the first window that is not 1,024 tokens), the published
sizes from shapes alone, a block of peers against peer by peer, one
`round_step` against the plain reference's round, `Trainer`, `Simulator` and
`HiveStepper` through the one `Model` interface, and the round's gauges.
The parity of the model with the plain reference is
tests/test_v5_mimo_v2.py's (whose module doc says why these two files are
named to be collected last)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as ref
from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import laguna, lm, mimo_v2, qwen3_next
from biscotti_tpu.models.peer_step import (BLOCK_SHARE, DEVICE_BYTES,
                                           peer_block)
from biscotti_tpu.models.trainer import (Trainer, block_step_fn,
                                         local_step_fn)
from biscotti_tpu.models.zoo import DEFAULTS, MODELS, model_for_dataset
from biscotti_tpu.parallel.sim import Simulator

from test_v5_mimo_v2 import DATASET, NAME, TINY, published


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
    assert counts["load"].shape == (4, 4) and int(counts["dropped"].sum()) == 0
    for peer in range(3):
        np.testing.assert_allclose(deltas[peer],
                                   one(w, xb[peer], yb[peer], frozen),
                                   atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(deltas, axis=1), 0.1 * 0.005,
                               rtol=1e-4)  # every peer's step is clipped


def test_the_attention_is_walked_and_every_scope_is_in_the_round():
    """A block's layers run their attention under `lm.peer_at_a_time`, and
    every scope and part the model declares is in the compiled round, the
    core under its kind's name."""
    sim = Simulator(_cfg(batch_size=2))
    hlo = sim.round_hlo()
    for scope in mimo_v2.SCOPES:
        assert scope in hlo, scope
    for part in mimo_v2.SUBSCOPES:
        assert f"lm_attention/{part}" in hlo, part
    assert "attn_core/" not in hlo  # a kind each, and no third name
    assert sim.peer_block > 1
    import re

    walked = [name for name in re.findall(r'op_name="([^"]*)"', hlo)
              if "peer_walk" in name]
    assert any("attn_core_swa" in name for name in walked)
    assert any("attn_core_full" in name for name in walked)
    assert not any("lm_experts" in name for name in walked)


# ------------------------------------------------- the system's own path


def _cfg(**kw):
    base = dict(dataset=DATASET, model_name=NAME, num_nodes=6, batch_size=8,
                epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
                num_miners=1, num_noisers=1, learning_rate=0.1,
                grad_clip=0.05, seed=9)
    return BiscottiConfig(**{**base, **kw})


def test_the_zoo_registers_both_presets_and_their_datasets():
    assert set(mimo_v2.PRESETS) <= set(MODELS)
    assert DEFAULTS["lm_tokens_mimo"] == "mimo_v2_fedlora"
    model = model_for_dataset(DATASET, NAME)
    assert model.name == NAME and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    assert model.num_params == 2 * 2 * (68 + 32) + 3 * 2 * (88 + 32) == 1120
    assert model.info["attention"]["fused"] == 0
    assert callable(model.info["sink_mass"])
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", NAME)
    with pytest.raises(ValueError, match="19072"):
        model_for_dataset(DATASET, "mimo_v2_fedlora")
    with pytest.raises(ValueError, match="19072"):  # another model's slice
        model_for_dataset("lm_tokens_qwen3next", "mimo_v2_fedlora")
    # the scopes are the siblings' where the work is the same, the core's
    # two names this model's own
    assert set(mimo_v2.SCOPES) == set(laguna.SCOPES)
    assert set(mimo_v2.SUBSCOPES) - set(laguna.SUBSCOPES) == {
        "attn_core_swa", "attn_core_full"}
    assert set(laguna.SUBSCOPES) - set(mimo_v2.SUBSCOPES) == {"attn_core"}
    assert not {"attn_core_swa", "attn_core_full"} & set(
        qwen3_next.SUBSCOPES)


def test_the_dataset_holds_windows_of_2048_tokens():
    """The first window that is not 1,024 tokens: `lm_tokens_mimo`, 80
    windows a peer (64 the train cut) of 2,048 ids below 19,072, a label a
    position (the window one position on), the same by name every time and
    another for another peer."""
    spec = ds.spec("lm_tokens_mimo")
    assert spec.tokens and spec.n_classes == 19072 and spec.d_in == 2048
    assert spec.shard_size == 80 and spec.test_size == 2
    shard = ds.load_shard("lm_tokens_mimo", "lm_tokens_mimo3")
    x, y = shard["x_train"], shard["y_train"]
    assert x.shape == y.shape == (64, 2048) and x.dtype == np.int32
    assert 0 <= x.min() and x.max() < 19072
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    again = ds.load_shard("lm_tokens_mimo", "lm_tokens_mimo3")
    np.testing.assert_array_equal(again["x_train"], x)
    other = ds.load_shard("lm_tokens_mimo", "lm_tokens_mimo4")
    assert (other["x_train"] != x).mean() > 0.5
    test = ds.load_shard("lm_tokens_mimo", "lm_tokens_mimo_test")
    assert test["x_test"].shape == (2, 2048)


def test_the_published_sizes_from_shapes_alone():
    """What the dataset trains where no model is named: the leading dense
    layer and one whole period at the published widths, 5,847,250,752
    frozen parameters (11.69 GB in bfloat16) and d = 2,080,768; no
    parameter is drawn to learn it. ISSUE 40's table, part by part."""
    big = model_for_dataset("lm_tokens_mimo")
    cfg = big.info["config"]
    assert big.name == "mimo_v2_fedlora" and cfg.layers == 7
    assert big.d_in == 2048 and big.n_classes == 19072
    assert [cfg.kind(at) for at in range(7)] == [
        ("full", False), ("window", True), ("window", True),
        ("window", True), ("window", True), ("full", True),
        ("window", True)]
    assert big.num_params == 2 * 16 * 17664 + 5 * 16 * 18944 == 2080768
    assert lm.frozen_count(big) == 5847250752
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree.leaves(shapes)} == {
        jnp.dtype(jnp.bfloat16)}

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    def attention(layer):
        return count({n: layer[n] for n in ("w_qkv", "wo", "sink")
                      if n in layer})

    def sparse(layer):
        return count({n: layer[n] for n in ("router", "router_bias",
                                            "experts")})

    first, window, full = (shapes["layers"][at] for at in (0, 1, 5))
    assert first["w_qkv"].shape == full["w_qkv"].shape == (4096, 13568)
    assert window["w_qkv"].shape == (4096, 14848)
    assert first["wo"].shape == window["wo"].shape == (8192, 4096)
    assert window["sink"].shape == (64,) and "sink" not in full
    assert attention(full) == attention(first) == 89128960
    assert attention(window) == 94371904
    assert count(first["dense"]) == 3 * 4096 * 16384 == 201326592
    assert count(first) - count(first["lora_a"]) == 290463744
    assert window["router"].shape == (4096, 256)
    assert window["router_bias"].shape == (256,)
    assert window["experts"]["w_gate"].shape == (32, 4096, 2048)
    assert window["experts"]["w_down"].shape == (32, 2048, 4096)
    assert sparse(window) == sparse(full) == 806355200
    assert count(window) - count(window["lora_a"]) == 900735296
    assert count(full) - count(full["lora_a"]) == 895492352
    assert "shared" not in window and "dense" not in window
    assert count([layer["lora_a"] for layer in shapes["layers"]]) == 1376256
    assert shapes["embed"].shape == (19072, 4096)
    assert shapes["head"].shape == (4096, 19072)
    assert 290463744 + 5 * 900735296 + 895492352 + 1376256 \
        + 2 * 19072 * 4096 + 4096 == 5847250752
    # both cores are the kernel, a query head at a time
    plan = big.info["attention"]
    assert plan["fused"] == 1
    assert plan["kinds"]["window"]["blocks"] == (256, 256)
    assert plan["kinds"]["full"]["group"] == 1
    assert big.step_bytes(2) == 2 * big.step_bytes(1)


def test_one_round_step_is_the_references_round():
    """`Simulator.round_step` from seeded adapters against
    benchmark/reference/mimo_v2.py's round: the same sampled peers,
    windows and noise (re-derived through reference/round.py), the dense
    scores with the sink's column, Krum's oracle, the sum, the ledger."""
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


def test_the_round_trains_the_adapters_and_reports_its_cores():
    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    sim = Simulator(_cfg(batch_size=2), metrics=registry)
    w, stake, logs = sim.run(num_rounds=2, stop_at_convergence=False)
    assert w.shape == (1120,) and np.isfinite(w).all() and np.asarray(w).any()
    assert logs[-1].accepted == 4 - 4 // 2
    page = registry.render()
    for name in ("biscotti_sim_frozen_bytes", "biscotti_sim_peer_block",
                 "biscotti_lm_attention_fused 0",
                 "biscotti_lm_attention_shared_key 0",
                 'biscotti_attn_block_share{kind="window"} 1',
                 'biscotti_attn_block_share{kind="full"} 1',
                 'biscotti_attn_seen_share{kind="window"} 0.2265625',
                 'biscotti_attn_group{kind="full"} 4',
                 'biscotti_attn_group{kind="window"} 2',
                 "biscotti_attn_sink_mass 0.",
                 "biscotti_moe_tokens_dropped 0",
                 "biscotti_moe_load_max_over_mean",
                 "biscotti_moe_uncut_calls",
                 "biscotti_moe_tile_fill", "biscotti_moe_grouped_kernel 0"):
        assert name in page, name
    assert "biscotti_gdn_chunks" not in page
    stats = sim.dispatch_stats()
    assert stats["tokens_dropped"] == 0 and stats["assignments_held"] > 0
    # a sibling states no kinds of core and no sink
    other = MetricsRegistry()
    Simulator(_cfg(model_name="laguna_tiny", batch_size=2),
              metrics=other).run(num_rounds=1, stop_at_convergence=False)
    assert "biscotti_attn_block_share" not in other.render()
    assert "biscotti_attn_sink_mass" not in other.render()


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
    """The published preset's `step_bytes` (1.71 GB a peer at 2,048 tokens)
    against what the chip's runtime states less the standing arrays (11.69
    GB of base, the stacks, the deltas and noise: 4.64 GB free): one peer
    is 0.37 of the free bytes and three are 1.1, so the cell walks its
    peers ONE at a time, as the compiled round says it must (2.22 GB of
    temporaries at a block of 1; a block of 3 does not compile)."""
    big = model_for_dataset("lm_tokens_mimo")
    step = big.step_bytes(1)
    free = DEVICE_BYTES - (2 * 5847250752 + 30 * 2 * 64 * 2048 * 4
                           + 4 * (3 * 21 + 2) * 2080768)
    assert 1.6e9 < step < 1.8e9 and 4.6e9 < free < 4.7e9
    assert step / free < BLOCK_SHARE < 3 * step / free
    assert peer_block(21, step, free) == 1
    assert peer_block(21, step, 3 * free) == 3


def test_the_hive_stepper_steps_the_model_as_the_trainer_does():
    """`HiveStepper` through the same `Model` interface: one batched
    dispatch whose rows are each co-hosted peer's own Trainer's delta."""
    import asyncio

    from biscotti_tpu.runtime.hive import HiveStepper

    n = 3
    cfg = _cfg(num_nodes=n, batch_size=2, grad_clip=1.0, noising=False,
               verification=False, base_port=13980, seed=3)
    stepper = HiveStepper(cfg, range(n))
    assert stepper.num_params == 1120
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
