"""DeepSeek-V2's share on the system's own path (the tiny preset): the zoo
and its datasets, a block of peers against peer by peer on both sides of
the attention's dispatch, the model's `step_bytes`, `Trainer`, `Simulator`
and `HiveStepper` through the one `Model` interface, and the round's
counts and gauges. The parity with the plain reference is
tests/test_v2_deepseek.py's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2 as ref
from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import deepseek_v2, laguna, lm
from biscotti_tpu.models.trainer import (Trainer, block_step_fn,
                                         local_step_fn)
from biscotti_tpu.models.zoo import MODELS, model_for_dataset
from biscotti_tpu.ops import moe
from biscotti_tpu.parallel.sim import Simulator

DATASET = "lm_tokens_tiny"
NAME = "deepseek_v2_tiny"
TINY = deepseek_v2.PRESETS[NAME]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "q_lora_rank": cfg.q_rank, "kv_lora_rank": cfg.kv_rank,
        "qk_nope_head_dim": cfg.nope, "qk_rope_head_dim": cfg.rope,
        "v_head_dim": cfg.v_dim, "num_hidden_layers": cfg.layers,
        "first_k_dense_replace": len(cfg.dense_layers),
        "n_group": cfg.groups, "topk_group": cfg.groups_kept,
        "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scale,
        "norm_topk_prob": cfg.norm_topk, "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling, type="yarn"),
        "rms_norm_eps": cfg.eps, "first_expert": cfg.first_expert,
        "lora_rank": cfg.rank, "lora_alpha": cfg.alpha}


@pytest.fixture(scope="module")
def tiny():
    model = model_for_dataset(DATASET, NAME)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def _ref64(cfg):
    return ref.compiled(published(cfg), jnp.float64)


def _wide(t=128):
    """The tiny preset at the published head shapes (192 | 128) on windows
    of `t`: what ops/attention.py's kernel takes (interpreted here)."""
    cfg = dataclasses.replace(TINY, nope=128, rope=64, v_dim=128, heads=2)
    model = deepseek_v2.deepseek_v2_model("deepseek_v2_wide", cfg, t)
    assert model.info["attention"] == {"fused": 1, "block_share": 1.0,
                                       "shared_key": 1}
    tokens = jax.random.randint(jax.random.PRNGKey(3), (6, t + 1), 0,
                                cfg.vocab, jnp.int32)
    return (model, model.frozen(jax.random.PRNGKey(1)),
            model.flat_init(jax.random.PRNGKey(2)), tokens[:, :-1],
            tokens[:, 1:])


@pytest.mark.parametrize("side", ["einsum", "kernel"])
def test_a_block_of_peers_is_each_peer_alone(tiny, side):
    """One dispatch over the block's tokens, the per-peer part confined to
    the adapters: every row of the block's deltas is that peer's own step
    (on the kernel's side too: its grid walks the windows)."""
    model, frozen, w, x, y = tiny if side == "einsum" else _wide()
    assert model.info["attention"]["fused"] == (side == "kernel")
    block = jax.jit(block_step_fn(model, "clipped_sgd", 0.05, 0.1))
    one = local_step_fn(model, "clipped_sgd", 0.05, 0.1)
    xb = jnp.asarray(x[:6]).reshape(3, 2, -1)
    yb = jnp.asarray(y[:6]).reshape(3, 2, -1)
    deltas, counts = block(w, xb, yb, frozen)
    assert deltas.shape == (3, model.num_params)
    for peer in range(3):
        np.testing.assert_allclose(deltas[peer],
                                   one(w, xb[peer], yb[peer], frozen),
                                   atol=1e-7)
    np.testing.assert_allclose(jnp.linalg.norm(deltas, axis=1), 0.1 * 0.05,
                               rtol=1e-4)
    assert counts["load"].shape == (2, 4)
    assert int(counts["dropped"].sum()) == 0
    # the sorted buffer is cut as Laguna's is: CAPACITY x the rows a
    # uniform router sends the 4 held of 16 experts, three a token
    cut = moe.CAPACITY * (6 * xb.shape[-1] * 3 / 16) * 4
    np.testing.assert_array_equal(counts["buffer_rows"], [cut, cut])
    assert cut < 6 * xb.shape[-1] * 3 and not counts["uncut"].any()
    # 3 peers x 2 windows of tokens a sparse layer; 1 to 2 groups a token
    tokens = 6 * xb.shape[-1]
    np.testing.assert_array_equal(counts["tokens"], [tokens, tokens])
    assert all(tokens <= g <= 2 * tokens for g in counts["groups_spanned"])


def test_the_kernel_side_matches_the_reference_at_the_published_heads():
    model, frozen, w, x, y = _wide()
    cfg = model.info["config"]
    tokens, labels = x[:1], y[:1]
    want, _ = ref.compiled(published(cfg), jnp.float64)[1](frozen, w, tokens)
    np.testing.assert_allclose(model.apply_flat(w, tokens, frozen), want,
                               atol=5e-5)
    got = jax.grad(model.loss_flat)(w, tokens, labels, frozen)
    want = ref.compiled(published(cfg), jnp.float64)[0](frozen, w, tokens,
                                                        labels)
    np.testing.assert_allclose(got, want, atol=1e-6 + 1e-4 * float(
        jnp.max(jnp.abs(want))))


# ------------------------------------------------- the system's own path


def _cfg(**kw):
    base = dict(dataset=DATASET, model_name=NAME, num_nodes=6, batch_size=8,
                epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
                num_miners=1, num_noisers=1, learning_rate=0.1,
                grad_clip=0.05, seed=9)
    return BiscottiConfig(**{**base, **kw})


def test_the_zoo_registers_both_presets_and_their_datasets():
    assert set(deepseek_v2.PRESETS) <= set(MODELS)
    model = model_for_dataset(DATASET, NAME)
    assert model.name == NAME and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    assert model.num_params == 660
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", NAME)
    with pytest.raises(ValueError, match="25600"):
        model_for_dataset(DATASET, "deepseek_v2_fedlora")
    spec = ds.spec("lm_tokens_dsv2")
    assert spec.tokens and spec.n_classes == 25600 and spec.d_in == 1024
    # what the dataset trains where no model is named, from shapes alone
    big = model_for_dataset("lm_tokens_dsv2")
    assert big.name == "deepseek_v2_fedlora"
    assert big.num_params == 5166080
    assert lm.frozen_count(big) == 5166269440
    assert big.info["attention"] == {"fused": 1, "block_share": 0.75,
                                     "shared_key": 1}
    # the scopes are the model's own, and Laguna's stay Laguna's
    assert "mla_core" in deepseek_v2.SCOPES
    assert "mla_core" not in laguna.SCOPES


def test_step_bytes_has_no_term_for_the_scores():
    """A peer's activation bytes grow with its tokens and with nothing
    else: twice the window is twice the bytes (a term for [heads, T, T]
    scores would make it four times), as twice the batch is."""
    cfg = deepseek_v2.PRESETS["deepseek_v2_fedlora"]
    short = deepseek_v2.deepseek_v2_model("a", cfg, 1024).step_bytes
    long = deepseek_v2.deepseek_v2_model("b", cfg, 2048).step_bytes
    assert long(1) == 2 * short(1) == short(2)
    assert short(1) < 2 * 4 * cfg.heads * 1024 * 1024  # one scores array


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


def test_the_round_trains_the_adapters_and_reports_its_routing():
    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    sim = Simulator(_cfg(batch_size=2), metrics=registry)
    w, stake, logs = sim.run(num_rounds=2, stop_at_convergence=False)
    assert w.shape == (660,) and np.isfinite(w).all() and np.asarray(w).any()
    assert logs[-1].accepted == 4 - 4 // 2
    page = registry.render()
    for name in ("biscotti_sim_frozen_bytes", "biscotti_sim_peer_block",
                 "biscotti_lm_attention_fused 0",
                 "biscotti_lm_attention_block_share 1",
                 "biscotti_lm_attention_shared_key 1",
                 "biscotti_moe_assignments_held",
                 "biscotti_moe_load_max_over_mean",
                 "biscotti_moe_groups_kept",
                 "biscotti_moe_tokens_dropped 0"):
        assert name in page, name
    stats = sim.dispatch_stats()
    assert 1.0 <= stats["groups_kept"] <= TINY.groups_kept
    assert stats["load_max_over_mean"] >= 1.0
    assert 0 < stats["assignments_held"] < 768
    # four peers x 2 windows of 16 tokens, three of 16 experts a token, 4
    # held: the round's calls ran on CAPACITY x 96 rows, none on all 384
    assert stats["buffer_rows"] == moe.CAPACITY * 96 == 192
    assert stats["uncut_calls"] == 0
    assert "biscotti_moe_buffer_rows 192" in page
    assert "biscotti_moe_uncut_calls 0" in page
    # a model whose router has one group reports no groups
    other = Simulator(_cfg(model_name="laguna_tiny", batch_size=2))
    other.run(num_rounds=1, stop_at_convergence=False)
    assert "groups_kept" not in other.dispatch_stats()


def test_the_hive_stepper_steps_the_model_as_the_trainer_does():
    """`HiveStepper` through the same `Model` interface: one batched
    dispatch whose rows are each co-hosted peer's own Trainer's delta."""
    import asyncio

    from biscotti_tpu.runtime.hive import HiveStepper

    n = 3
    cfg = _cfg(num_nodes=n, batch_size=2, grad_clip=1.0, noising=False,
               verification=False, base_port=13910, seed=3)
    stepper = HiveStepper(cfg, range(n))
    assert stepper.num_params == 660  # this model's adapters, not Laguna's
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
