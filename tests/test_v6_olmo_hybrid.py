"""Olmo-Hybrid-7B's gated delta-net / plain full-attention hybrid with
adapters (models/olmo_hybrid.py, ops/delta_rule.py, ops/attention.py)
against the plain float64 reference (benchmark/reference/olmo_hybrid.py:
the delta rule a token at a time, the scores whole), at the tiny preset:
one period of three delta-net layers and a full layer, four chunks a
16-token window, a state of 6 x 12, beta in (0, 2), the norm on every
sub-block's output, an untied head.

(Named `test_v6_...` so that it is collected LAST: PR 31's lesson,
.claude/skills/verify/SKILL.md.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import lm, olmo_hybrid
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import delta_rule

DATASET = "lm_tokens_tiny"
NAME = "olmo_hybrid_tiny"
TINY = olmo_hybrid.PRESETS[NAME]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "layer_types": [f"{kind}_attention" for kind in cfg.layer_types],
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads,
        "linear_num_key_heads": cfg.key_heads,
        "linear_num_value_heads": cfg.value_heads,
        "linear_key_head_dim": cfg.key_dim,
        "linear_value_head_dim": cfg.value_dim,
        "linear_conv_kernel_dim": cfg.conv, "rms_norm_eps": cfg.eps,
        "lora_rank": cfg.rank, "lora_alpha": cfg.alpha}


@pytest.fixture(scope="module")
def tiny():
    model = model_for_dataset(DATASET, NAME)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def _ref64(variant=None):
    return ref.compiled(published(TINY), jnp.float64, variant)


def test_a_window_that_is_no_whole_number_of_chunks_is_refused():
    with pytest.raises(ValueError, match="whole number"):
        olmo_hybrid.olmo_hybrid_model("a", TINY, 18)


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    """float32 against float64 on the same weights: 5e-5 absolute on logits
    of magnitude 3 (read at 1.4e-5)."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:windows])
    got = model.apply_flat(w, tokens, frozen)
    want = _ref64()[1](frozen, w, tokens)
    assert got.shape == (windows, 16, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_loss_matches_the_reference(tiny):
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:3]), jnp.asarray(y[:3])
    spec = published(TINY)
    want = jax.jit(lambda frozen, w, tokens, labels: ref.loss(
        spec, frozen, ref.unflatten(spec, w, jnp.float64), tokens, labels,
        jnp.float64))(frozen, w, tokens, labels)
    np.testing.assert_allclose(model.loss_flat(w, tokens, labels, frozen),
                               want, rtol=1e-5)


@pytest.mark.parametrize("windows", [1, 2])
def test_every_adapter_gradient_matches_the_reference(tiny, windows):
    """Through the chunked rule's backward at beta up to 2 (its solve's
    included), the conv's, the gated norm's, the attention's and the four
    output norms', against `jax.grad` of the token-by-token reference:
    float32's rounding on gradients up to 1.4 (read at 1.4e-5), relative
    to each leaf's largest."""
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    got = jax.grad(model.loss_flat)(w, tokens, labels, frozen)
    want = _ref64()[0](frozen, w, tokens, labels)
    spec = published(TINY)
    assert ref.num_params(spec) == model.num_params == got.shape[0]
    for (name, g), (_, r) in zip(ref.leaves(spec, np.asarray(got)),
                                 ref.leaves(spec, np.asarray(want))):
        assert np.linalg.norm(r) > 0, name  # every B counts in the loss
        np.testing.assert_allclose(g, r, atol=2e-6 + 1e-4 * np.abs(r).max(),
                                   err_msg=name)


def test_the_wire_vector_is_the_references_layout(tiny):
    model, _, w, _, _ = tiny
    tree = model.unravel(w)
    names = [name for name, _ in ref.layout(published(TINY))]
    assert names[:3] == ["layers[0].out", "layers[0].qkvz", "layers[1].out"]
    assert names[6:] == [f"layers[3].{n}" for n in "koqv"]
    assert ref.kinds(published(TINY)) == list(TINY.layer_types) \
        == ["linear"] * 3 + ["full"]
    for name, piece in ref.leaves(published(TINY), np.asarray(w)):
        layer, leaf = name.split(".")
        mine = tree["layers"][int(layer[len("layers["):-1])][leaf]
        np.testing.assert_array_equal(np.ravel(mine), piece, err_msg=name)


def test_the_reference_reads_the_layers_held_of_the_published_pattern():
    """`layer_types` stays whole in the configuration's file (32 entries)
    and `num_hidden_layers` says how many are held: the first."""
    spec = dict(published(TINY), num_hidden_layers=2)
    assert ref.kinds(spec) == ["linear", "linear"]
    assert ref.num_params(spec) == 2 * 2 * (108 + 32)


def test_the_head_is_untied_and_every_norm_reads_its_weight_as_w(tiny):
    model, frozen, w, x, _ = tiny
    assert frozen["head"].shape == (32, 64)
    assert frozen["embed"].shape == (64, 32)
    for leaf in ("norm", "mlp_norm"):  # around ONE, not zero-centred
        assert abs(float(jnp.mean(frozen["layers"][0][leaf])) - 1.0) < 0.1
    tokens = jnp.asarray(x[:1])
    h = olmo_hybrid.hidden_states(TINY, lm.one_peer(model.unravel(w)),
                                  tokens[None], frozen, remat=False)[0]
    want = lm.rms(h[0], frozen["final_norm"], TINY.eps) @ frozen["head"]
    np.testing.assert_allclose(model.apply_flat(w, tokens, frozen), want,
                               atol=1e-5)


def test_a_layer_adds_the_norm_of_what_its_sub_blocks_give(tiny):
    """`h + rms(f(h), w)`: with the two output norms' weights at ZERO a
    layer of either kind is the identity, whatever its mixer and its MLP
    compute (under the pre-norm order it would not be)."""
    model, frozen, w, x, _ = tiny
    h = frozen["embed"][jnp.asarray(x[:2])][None]            # [1, 2, T, H]
    adapters = lm.one_peer(model.unravel(w))
    for at in (0, 3):
        layer = dict(frozen["layers"][at])
        out, _, _ = olmo_hybrid._layer(TINY, at, h, layer,
                                       adapters["layers"][at])
        assert float(jnp.max(jnp.abs(out - h))) > 0.5
        layer.update(norm=jnp.zeros_like(layer["norm"]),
                     mlp_norm=jnp.zeros_like(layer["mlp_norm"]))
        out, _, _ = olmo_hybrid._layer(TINY, at, h, layer,
                                       adapters["layers"][at])
        np.testing.assert_array_equal(out, h)


# what `config.json` does not state (the configuration's `assumed`), each a
# switch of the reference, then the controls; the least each must move the
# logits by, relative (read at 0.91, 1.25, 0.91, 0.196, 0.27, 0.097, 0.84,
# 0.65, 0.62, 0.0021, 0.88, 0.96)
DEPARTURES = [
    ("norm_before_mixer", {"norm_first": True}, 0.1),
    ("flat_layout", {"layout": "flat"}, 0.1),
    ("gate_before_norm", {"gate_first": True}, 0.1),
    ("rotary_on", {"rotary": 500000.0}, 0.02),
    ("no_qk_norm", {"qk_norm": False}, 0.05),
    ("qk_norm_a_head", {"qk_norm": "head"}, 0.02),
    ("no_delta", {"delta": False}, 0.1),
    ("beta_one", {"beta": 1.0}, 0.1),
    ("beta_not_doubled", {"beta_scale": 1.0}, 0.1),
    ("decay_bfloat16", {"decay": "bfloat16", "chunk": 4}, 5e-4),
    ("no_carry", {"carry": False, "chunk": 4}, 0.1),
    ("no_l2norm", {"l2norm": False}, 0.1),
]


@pytest.mark.parametrize("name,variant,least",
                         DEPARTURES, ids=[d[0] for d in DEPARTURES])
def test_every_departure_of_the_reference_moves_the_logits(tiny, name,
                                                           variant, least):
    """The program sits on the reference (1e-5, relative) and every
    switch's departure far from both: the output-norm order, the fused
    layout, the norm's place in the mixer, no rotary, the q and k norms
    over the whole projection, the correction, the doubled beta, the
    carried state and the two l2 norms are in the program."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    want = np.asarray(_ref64()[1](frozen, w, tokens))
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    other = np.asarray(_ref64(variant)[1](frozen, w, tokens))
    scale = np.linalg.norm(want)
    assert np.linalg.norm(got - want) / scale < 1e-5
    assert np.linalg.norm(other - want) / scale > least, name


def test_the_steps_law_is_what_lets_the_comparison_see_the_state(tiny):
    """`dt_bias` by Mamba-2's law (the configuration's `assumed`): with
    ones in its place a head forgets within a token, and leaving the
    carried state out moves the logits by a fraction of what it moves them
    under the law."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    ones = dict(frozen, layers=[
        dict(layer, dt_bias=jnp.ones_like(layer["dt_bias"]))
        if "dt_bias" in layer else layer for layer in frozen["layers"]])

    def moved(tree):
        want = np.asarray(_ref64()[1](tree, w, tokens))
        other = np.asarray(_ref64({"carry": False, "chunk": 4})[1](
            tree, w, tokens))
        return np.linalg.norm(other - want) / np.linalg.norm(want)

    assert moved(frozen) > 0.3
    assert moved(ones) < 0.5 * moved(frozen)


def test_the_published_dtype_runs_close_to_the_reference():
    """bfloat16 base and operands, float32 accumulation (the published
    size's arithmetic, here at the tiny widths): within bfloat16's
    resolution of the float64 reference on the same rounded weights."""
    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    model = olmo_hybrid.olmo_hybrid_model("olmo_hybrid_tiny_bf16", cfg, 16)
    frozen = model.frozen(jax.random.PRNGKey(1))
    assert frozen["layers"][0]["a_log"].dtype == jnp.bfloat16
    w = model.flat_init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(ds.load_shard(DATASET, f"{DATASET}0")["x_train"][:2])
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    want = np.asarray(_ref64()[1](frozen, w, tokens))
    gap = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert 1e-4 < np.median(gap) < 3e-2, np.median(gap)


def test_the_frozen_scalars_follow_their_laws():
    layers = model_for_dataset(DATASET, NAME).frozen(
        jax.random.PRNGKey(7))["layers"]
    layer, full = layers[0], layers[3]
    a = np.exp(np.asarray(layer["a_log"], np.float64))
    assert ((a > 0.0) & (a <= 16.0)).all()
    step = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))
    assert ((step >= 0.99e-3) & (step <= 0.101)).all()  # Mamba-2's, not 1
    assert abs(float(np.mean(layer["gate_norm"])) - 1.0) < 0.2
    assert layer["gate_norm"].shape == (12,)
    assert layer["conv_w"].shape == (4, 2 * 3 * 6 + 3 * 12)
    assert "conv_b" not in layer and layer["w_ba"].shape == (32, 6)
    assert layer["w_qkvz"].shape == (32, 2 * 18 + 2 * 36)
    assert layer["w_out"].shape == (36, 32)
    assert full["wq"].shape == full["wk"].shape == (32, 32)
    # over the WHOLE projection, not a head's
    assert full["q_norm"].shape == full["k_norm"].shape == (32,)


@pytest.mark.parametrize("case", ["published", "tiny"])
def test_a_model_says_which_side_of_the_rules_dispatch_it_runs(case):
    """`model.info["gdn_rule"]`: the kernel at the published shapes, each
    head of 96 | 192 laid in 128 | 256 with zero columns and five value
    heads a step of the grid; the `jax.numpy` form at the tiny preset's (D
    = 6, chunks of 4)."""
    if case == "published":
        info = olmo_hybrid.olmo_hybrid_model(
            "a", olmo_hybrid.PRESETS["olmo_hybrid_fedlora"],
            1024).info["gdn_rule"]
        assert info == {"kernel": 1, "states_saved": 1,
                        "key_heads_a_step": 5, "value_heads_a_step": 5,
                        "padded_share": 0.4375}
        assert info["value_heads_a_step"] >= delta_rule.IN_STEP
    else:
        info = olmo_hybrid.olmo_hybrid_model("a", TINY, 16).info["gdn_rule"]
        assert info == {"kernel": 0, "states_saved": 0,
                        "key_heads_a_step": 0, "value_heads_a_step": 0,
                        "padded_share": 0.0}
