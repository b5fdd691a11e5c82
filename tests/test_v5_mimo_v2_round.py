"""MiMo-V2.5's decoder on the system's own path (the tiny preset): the
family's round cases (tests/lm_family.py) over this model's record, and
what only this model has: the first dataset whose window is not 1,024
tokens, the published sizes part by part, the kinds of core and the sink on
the metrics page. The parity of the model with the plain reference is
tests/test_v5_mimo_v2.py's."""

import jax
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import laguna, mimo_v2, qwen3_next
from biscotti_tpu.models.peer_step import BLOCK_SHARE
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
from test_v5_mimo_v2 import DATASET, NAME, published

FAMILY = Family(
    module=mimo_v2, ref=ref, name=NAME, published=published,
    num_params=2 * 2 * (68 + 32) + 3 * 2 * (88 + 32), load=(4, 4),
    port=13980,
    big=("lm_tokens_mimo", "mimo_v2_fedlora", 19072, 2080768, 5847250752),
    # the core under its kind's name, and no third name
    walked=("attn_core_swa", "attn_core_full"), not_walked=("lm_experts",),
    not_in_round=("attn_core/",),
    # 1.71 GB a peer at 2,048 tokens against 4.64 GB free beside 11.69 GB
    # of base: one peer is 0.37 of the free bytes and three are 1.1, so the
    # cell walks its peers ONE at a time, as the compiled round says it must
    # (a block of 3 does not compile)
    block_rule=((1.6e9, 1.8e9), (4.6e9, 4.7e9), (0.0, 2 * BLOCK_SHARE),
                {1.0: 1, 3.0: 3}),
    gauges=("biscotti_lm_attention_fused 0",
            "biscotti_lm_attention_shared_key 0",
            'biscotti_attn_block_share{kind="window"} 1',
            'biscotti_attn_block_share{kind="full"} 1',
            'biscotti_attn_seen_share{kind="window"} 0.2265625',
            'biscotti_attn_group{kind="full"} 4',
            'biscotti_attn_group{kind="window"} 2',
            "biscotti_attn_sink_mass 0.", "biscotti_moe_tokens_dropped 0",
            "biscotti_moe_load_max_over_mean", "biscotti_moe_uncut_calls",
            "biscotti_moe_tile_fill", "biscotti_moe_grouped_kernel 0"),
    no_gauges=("biscotti_gdn_chunks",),
    # a sibling states no kinds of core and no sink
    sibling=("laguna_tiny", ("biscotti_attn_block_share",
                             "biscotti_attn_sink_mass")))


def test_the_scopes_are_the_siblings_and_the_cores_this_models_own():
    model = model_for_dataset(DATASET, NAME)
    assert model.num_params == 1120
    assert model.info["attention"]["fused"] == 0
    assert callable(model.info["sink_mass"])
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


def test_the_published_sizes_part_by_part():
    """What the dataset trains where no model is named: the leading dense
    layer and one whole period at the published widths, 5,847,250,752
    frozen parameters (11.69 GB in bfloat16) and d = 2,080,768; no
    parameter is drawn to learn it. ISSUE 40's table, part by part."""
    big = model_for_dataset("lm_tokens_mimo")
    cfg = big.info["config"]
    assert cfg.layers == 7 and big.d_in == 2048
    assert [cfg.kind(at) for at in range(7)] == [
        ("full", False), ("window", True), ("window", True),
        ("window", True), ("window", True), ("full", True),
        ("window", True)]
    assert big.num_params == 2 * 16 * 17664 + 5 * 16 * 18944 == 2080768
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))

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


def test_the_round_trains_the_adapters_and_reports_its_cores():
    the_round_trains_the_adapters_and_reports(FAMILY)
