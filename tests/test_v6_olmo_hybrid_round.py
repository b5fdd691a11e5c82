"""Olmo-Hybrid-7B's hybrid on the system's own path (the tiny preset): the
family's round cases (tests/lm_family.py) over this model's record, and
what only this model has: its scopes, the published sizes part by part
(ISSUE 48's table), the rule's layout on the metrics page. The parity of
the model with the plain reference is tests/test_v6_olmo_hybrid.py's."""

import jax
import numpy as np

from benchmark.reference import olmo_hybrid as ref
from biscotti_tpu.models import granite_hybrid, laguna, olmo_hybrid, qwen3_next
from biscotti_tpu.models.zoo import model_for_dataset

from lm_family import (  # noqa: F401  (collected, run and counted here)
    Family, family, the_round_trains_the_adapters_and_reports, tiny,
    test_a_block_of_peers_is_each_peer_alone,
    test_one_round_step_is_the_references_round,
    test_the_attention_is_walked_and_every_scope_is_in_the_round,
    test_the_hive_stepper_steps_the_model_as_the_trainer_does,
    test_the_peer_block_is_what_the_step_bytes_leave_room_for,
    test_the_published_sizes_from_shapes_alone,
    test_the_walked_peer_axis_gives_the_same_deltas,
    test_the_zoo_registers_both_presets_and_their_datasets,
    test_trainer_step_is_the_simulators_for_the_same_batch)
from test_v6_olmo_hybrid import DATASET, NAME, published

# no router: a block counts nothing and nothing is dispatched
FAMILY = Family(
    module=olmo_hybrid, ref=ref, name=NAME, published=published,
    num_params=3 * 2 * (108 + 32) + 2 * 4 * 32, load=None, port=14010,
    # float32 sums in another order behind eight output norms (nothing
    # damps a sub-block's result before its norm divides by its length):
    # 5.2e-6 on entries up to 0.03, five of 1,096 (read on the tiny preset)
    stepper_atol=1e-5,
    big=("lm_tokens_olmo", "olmo_hybrid_fedlora", 100352, 5038080,
         4103615184),
    # the attention under the walk, the delta net the block's windows as
    # one batch
    walked=("attn_core",), not_walked=("gdn_",),
    # 1.58 GB a peer (read off the compiled round's memory analysis), 1.23
    # of it the logits over 100,352 classes: three peers are 0.642 of the
    # 7.38 GB free beside 8.21 GB of base, just over `BLOCK_SHARE`, so the
    # cell walks its peers ONE at a time; a tenth more room and it walks 3
    block_rule=((1.55e9, 1.6e9), (7.3e9, 7.4e9), (0.0, 0.1),
                {1.0: 1, 1.1: 3, 2.0: 3}),
    gauges=("biscotti_lm_attention_fused 0",
            "biscotti_lm_attention_shared_key 0", "biscotti_gdn_chunks 4",
            "biscotti_gdn_rule_kernel 0",
            "biscotti_gdn_value_heads_a_step 0",
            "biscotti_gdn_padded_share 0"),
    no_gauges=("biscotti_ssm_chunks", "biscotti_moe_"),
    # the other delta-net hybrid states neither of the layout's gauges
    sibling=("qwen3_next_tiny", ("biscotti_gdn_value_heads_a_step",
                                 "biscotti_gdn_padded_share")))


def test_the_scopes_are_the_siblings_and_the_subscopes_have_no_rotary():
    model = model_for_dataset(DATASET, NAME)
    assert model.num_params == 1096
    assert model.info["gdn_chunks"] == 4
    assert model.info["attention"] == {"fused": 0, "block_share": 1.0}
    # the delta net's scopes are Qwen3-Next's, the MLP's Granite's, and no
    # router or expert scope is declared
    assert set(olmo_hybrid.SCOPES) == set(qwen3_next.SCOPES) - {
        "lm_router", "lm_experts"}
    assert "lm_dense" in granite_hybrid.SCOPES
    assert set(laguna.SUBSCOPES) - set(olmo_hybrid.SUBSCOPES) == {
        "attn_rotary"}


def test_the_published_sizes_part_by_part():
    """What the dataset trains where no model is named: four whole periods
    at the published widths, the first of two pipeline stages:
    4,103,615,184 frozen parameters (8.21 GB in bfloat16) and d =
    5,038,080; no parameter is drawn to learn it. ISSUE 48's table, part
    by part, and the whole model it is cut from."""
    big = model_for_dataset("lm_tokens_olmo")
    cfg = big.info["config"]
    assert cfg.layers == 16
    assert cfg.layer_types == ("linear", "linear", "linear", "full") * 4
    assert big.num_params == 12 * 16 * (17280 + 3840) + 4 * 16 * 15360 \
        == 5038080
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    dense = ("mlp", "norm", "mlp_norm")
    linear, full = shapes["layers"][0], shapes["layers"][3]
    assert count({n: linear[n] for n in linear
                  if n not in dense + ("lora_a",)}) == 88750332
    assert count({n: full[n] for n in full
                  if n not in dense + ("lora_a",)}) == 58990080
    assert count({n: linear[n] for n in dense}) == 126819840
    assert count(linear) - count(linear["lora_a"]) == 215570172
    assert count(full) - count(full["lora_a"]) == 185809920
    period = 3 * 215570172 + 185809920
    assert period == 832520436 and 4 * period == 3330081744
    lora = count([layer["lora_a"] for layer in shapes["layers"]])
    assert lora == 12 * 16 * (3840 + 5760) + 4 * 16 * 4 * 3840 == 2826240
    outside = count({n: shapes[n] for n in ("embed", "head", "final_norm")})
    assert outside + lora == 773533440
    assert count(shapes) == 4 * period + 773533440 == 4103615184
    # the whole model: 32 layers, no adapters
    assert 8 * period + outside == 7430870688
    assert shapes["embed"].shape == (100352, 3840)
    assert shapes["head"].shape == (3840, 100352)
    assert linear["w_qkvz"].shape == (3840, 17280)
    assert linear["w_ba"].shape == (3840, 60)
    assert linear["conv_w"].shape == (4, 11520) and "conv_b" not in linear
    assert linear["a_log"].shape == linear["dt_bias"].shape == (30,)
    assert linear["gate_norm"].shape == (192,)
    assert linear["w_out"].shape == (5760, 3840)
    assert linear["mlp"]["w_gate"].shape == (3840, 11008)
    assert full["wq"].shape == full["wo"].shape == (3840, 3840)
    assert full["q_norm"].shape == full["k_norm"].shape == (3840,)
    assert big.info["gdn_chunks"] == 16
    # one query head a key/value head of 128: the core's kernel takes it
    assert big.info["attention"] == {"fused": 1, "block_share": 0.75}
    assert big.info["gdn_rule"]["kernel"] == 1


def test_the_round_trains_the_adapters_and_reports_its_rules_layout():
    the_round_trains_the_adapters_and_reports(FAMILY)
