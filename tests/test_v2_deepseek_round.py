"""DeepSeek-V2's share on the system's own path (the tiny preset): the
family's round cases (tests/lm_family.py) over this model's record, and
what only this model has: a block of peers on both sides of the attention's
dispatch, its `step_bytes`, the group-limited router's counts and gauges.
The parity with the plain reference is tests/test_v2_deepseek.py's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2 as ref
from biscotti_tpu.models import deepseek_v2, laguna
from biscotti_tpu.ops import moe
from biscotti_tpu.parallel.sim import Simulator

from lm_family import (  # noqa: F401  (collected, run and counted here)
    Family, a_block_of_peers_is_each_peer_alone, cfg_of, family,
    the_round_trains_the_adapters_and_reports, tiny,
    test_the_hive_stepper_steps_the_model_as_the_trainer_does,
    test_the_published_sizes_from_shapes_alone,
    test_the_zoo_registers_both_presets_and_their_datasets,
    test_trainer_step_is_the_simulators_for_the_same_batch)
from test_v2_deepseek import NAME, TINY, published

FAMILY = Family(
    module=deepseek_v2, ref=ref, name=NAME, published=published,
    num_params=660, load=(2, 4), clip=0.05, port=13910,
    big=("lm_tokens_dsv2", "deepseek_v2_fedlora", 25600, 5166080,
         5166269440),
    gauges=("biscotti_lm_attention_fused 0",
            "biscotti_lm_attention_block_share 1",
            "biscotti_lm_attention_shared_key 1",
            "biscotti_moe_assignments_held",
            "biscotti_moe_load_max_over_mean", "biscotti_moe_groups_kept",
            "biscotti_moe_tokens_dropped 0",
            # four peers x 2 windows of 16 tokens, three of 16 experts a
            # token, 4 held: the round's calls ran on CAPACITY x 96 rows,
            # none on all 384
            "biscotti_moe_buffer_rows 192", "biscotti_moe_uncut_calls 0"))


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
    """On the kernel's side too (its grid walks the windows), and what the
    group-limited router's dispatch counts beside its siblings'."""
    built = tiny if side == "einsum" else _wide()
    assert built[0].info["attention"]["fused"] == (side == "kernel")
    xb, counts = a_block_of_peers_is_each_peer_alone(FAMILY, built)
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


def test_the_published_plan_and_the_scopes_are_the_models_own():
    from biscotti_tpu.models.zoo import model_for_dataset

    big = model_for_dataset("lm_tokens_dsv2")
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


def test_the_round_trains_the_adapters_and_reports_its_routing():
    stats = the_round_trains_the_adapters_and_reports(FAMILY).dispatch_stats()
    assert 1.0 <= stats["groups_kept"] <= TINY.groups_kept
    assert stats["assignments_held"] < 768
    assert stats["buffer_rows"] == moe.CAPACITY * 96 == 192
    assert stats["uncut_calls"] == 0
    # a model whose router has one group reports no groups
    other = Simulator(cfg_of("laguna_tiny", batch_size=2))
    other.run(num_rounds=1, stop_at_convergence=False)
    assert "groups_kept" not in other.dispatch_stats()
