"""Granite-4.0-H-Micro's hybrid on the system's own path (the tiny
preset): the family's round cases (tests/lm_family.py) over this model's
record, and what only this model has: its scopes, the published layers, the
scan's chunks on the metrics page. The parity of the model with the plain
reference is tests/test_v3_granite_hybrid.py's."""

import jax

from benchmark.reference import granite_hybrid as ref
from biscotti_tpu.models import deepseek_v2, granite_hybrid, laguna
from biscotti_tpu.models.zoo import model_for_dataset

from lm_family import (  # noqa: F401  (collected, run and counted here)
    Family, family, the_round_trains_the_adapters_and_reports, tiny,
    test_a_block_of_peers_is_each_peer_alone,
    test_one_round_step_is_the_references_round,
    test_the_hive_stepper_steps_the_model_as_the_trainer_does,
    test_the_published_sizes_from_shapes_alone,
    test_the_walked_peer_axis_gives_the_same_deltas,
    test_the_zoo_registers_both_presets_and_their_datasets,
    test_trainer_step_is_the_simulators_for_the_same_batch)
from test_v3_granite_hybrid import DATASET, NAME, published

# no router: a block counts nothing and nothing is dispatched
FAMILY = Family(
    module=granite_hybrid, ref=ref, name=NAME, published=published,
    num_params=1272, load=None, port=13930, round_atol=1e-5,
    big=("lm_tokens_granite", "granite_h_micro_fedlora", 100352, 6410240,
         3195459328),
    gauges=("biscotti_lm_attention_fused 0",
            "biscotti_lm_attention_shared_key 0", "biscotti_ssm_chunks 4"),
    # the other language models state no chunks
    sibling=("laguna_tiny", ("biscotti_ssm_chunks",)))


def test_the_scopes_are_the_models_own_and_the_others_stay_theirs():
    model = model_for_dataset(DATASET, NAME)
    assert model.info["ssm_chunks"] == 4
    assert model.info["attention"] == {"fused": 0, "block_share": 1.0}
    assert {"ssm_scan", "ssm_proj", "ssm_conv", "ssm_gate"} <= set(
        granite_hybrid.SCOPES)
    assert not {"ssm_scan", "ssm_proj"} & set(laguna.SCOPES
                                              + deepseek_v2.SCOPES)


def test_the_published_layers_are_the_whole_model():
    """The WHOLE model, the embedding counted once: 36 state-space layers
    and an attention layer every tenth."""
    big = model_for_dataset("lm_tokens_granite")
    cfg = big.info["config"]
    assert cfg.layers == 40 and cfg.layer_types.count("mamba") == 36
    assert [at for at, kind in enumerate(cfg.layer_types)
            if kind == "attention"] == [5, 15, 25, 35]
    assert big.num_params == 36 * 16 * (8512 + 2048) + 4 * 16 * 5120
    shapes = jax.eval_shape(big.init_frozen, jax.random.PRNGKey(0))
    assert "head" not in shapes and shapes["embed"].shape == (100352, 2048)
    assert shapes["layers"][0]["w_in"].shape == (2048, 8512)
    assert shapes["layers"][5]["wk"].shape == (2048, 512)
    assert big.info["ssm_chunks"] == 4


def test_the_round_trains_the_adapters_and_reports_its_chunks():
    the_round_trains_the_adapters_and_reports(FAMILY)
