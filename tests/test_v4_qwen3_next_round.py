"""Qwen3-Next-80B-A3B-Instruct's hybrid on the system's own path (the tiny
preset): the family's round cases (tests/lm_family.py) over this model's
record, and what only this model has: its scopes, the published sizes part
by part, the rule's chunks on the metrics page. The parity of the model
with the plain reference is tests/test_v4_qwen3_next.py's."""

import jax
import numpy as np

from benchmark.reference import qwen3_next as ref
from biscotti_tpu.models import (deepseek_v2, granite_hybrid, laguna,
                                 qwen3_next)
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
from test_v4_qwen3_next import DATASET, NAME, published

FAMILY = Family(
    module=qwen3_next, ref=ref, name=NAME, published=published,
    num_params=6 * 2 * (96 + 32) + 2 * 2 * (64 + 16 + 16 + 32), load=(8, 4),
    port=13960,
    big=("lm_tokens_qwen3next", "qwen3_next_fedlora", 37984, 2605056,
         5424460992),
    # both mixers under the walk, a peer at a time; the router and the
    # experts on the block's tokens as one batch
    walked=("attn_core", "gdn_rule", "gdn_conv", "gdn_gate"),
    not_walked=("lm_experts", "lm_router"),
    # 0.970 GB a peer (read off the compiled round's memory analysis with
    # the rule a kernel): three peers are 0.542 of the free bytes, inside
    # `BLOCK_SHARE`, so the cell walks THREE at a time. A tenth less free
    # memory and the rule would take 1; seven are far out
    block_rule=((0.96e9, 1.0e9), (5.3e9, 5.4e9), (-0.07, 0.0),
                {1.0: 3, 2.0: 3, 0.9: 1}),
    gauges=("biscotti_lm_attention_fused 0",
            "biscotti_lm_attention_shared_key 0", "biscotti_gdn_chunks 4",
            "biscotti_gdn_rule_kernel 0", "biscotti_gdn_walked_layers 6",
            "biscotti_moe_tokens_dropped 0",
            "biscotti_moe_tile_fill", "biscotti_moe_grouped_kernel 0"),
    no_gauges=("biscotti_ssm_chunks",),
    # the other hybrid states no chunks of the rule
    sibling=("granite_h_tiny", ("biscotti_gdn_chunks",
                                "biscotti_gdn_rule_kernel",
                                "biscotti_gdn_walked_layers")))


def test_the_scopes_are_the_models_own_and_the_others_stay_theirs():
    model = model_for_dataset(DATASET, NAME)
    assert model.num_params == 2048
    assert model.info["gdn_chunks"] == 4
    assert model.info["attention"] == {"fused": 0, "block_share": 1.0}
    assert {"gdn_rule", "gdn_proj", "gdn_conv", "gdn_gate", "peer_walk"} \
        <= set(qwen3_next.SCOPES)
    assert not {"gdn_rule", "gdn_proj"} & set(
        laguna.SCOPES + deepseek_v2.SCOPES + granite_hybrid.SCOPES)
    assert qwen3_next.SUBSCOPES == laguna.SUBSCOPES


def test_the_published_sizes_part_by_part():
    """What the dataset trains where no model is named: three whole
    periods at the published widths, 5,424,460,992 frozen parameters
    (10.85 GB in bfloat16) and d = 2,605,056; no parameter is drawn to
    learn it. ISSUE 38's table, part by part."""
    big = model_for_dataset("lm_tokens_qwen3next")
    cfg = big.info["config"]
    assert cfg.layers == 12
    assert cfg.layer_types == ("gdn", "gdn", "gdn", "attention") * 3
    assert big.num_params == 9 * 16 * (12288 + 2048) \
        + 3 * 16 * (8192 + 512 + 512 + 2048) == 2605056
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))

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


def test_the_round_trains_the_adapters_and_reports_its_chunks():
    the_round_trains_the_adapters_and_reports(FAMILY)
