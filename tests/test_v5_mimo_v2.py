"""MiMo-V2.5's window / full attention decoder with adapters
(models/mimo_v2.py, ops/attention.py with a learned sink, ops/moe.py's
sigmoid router with a choice bias) against the plain float64 reference
(benchmark/reference/mimo_v2.py: the scores a dense [T, T] matrix with the
sink as one more column), at the tiny preset: five layers (full + dense,
window, window, full, window), a window of 4 on 16 tokens, head groups of 4
(full) and 2 (window), rotary on 4 of 12, 4 of 16 experts held, an untied
head.

(Named `test_v5_...` so that it is collected LAST: the driver's workers
take files in alphabetical order, and a new heavy file in the middle moves
the neighbours of tests/test_runtime.py's live clusters; PR 31's lesson,
.claude/skills/verify/SKILL.md.)"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mimo_v2 as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import mimo_v2
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import attention, moe

DATASET = "lm_tokens_tiny"
NAME = "mimo_v2_tiny"
TINY = mimo_v2.PRESETS[NAME]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads[0],
        "swa_num_key_value_heads": cfg.kv_heads[1],
        "head_dim": cfg.head_dim, "v_head_dim": cfg.value_dim,
        "partial_rotary_factor": cfg.rotary_factor,
        "rope_theta": cfg.rope_theta[0], "swa_rope_theta": cfg.rope_theta[1],
        "sliding_window": cfg.window,
        "attention_value_scale": cfg.value_scale,
        "hybrid_layer_pattern": list(cfg.pattern),
        "moe_layer_freq": list(cfg.sparse),
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "layernorm_epsilon": cfg.eps, "first_expert": cfg.first_expert,
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


# ------------------------------------------ the sink, ops/attention.plain


def _core_inputs(kv, g, t, d=12, e=8, windows=2, dtype=jnp.float64):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    return (jax.random.normal(keys[0], (windows, kv, g, t, d), dtype),
            jax.random.normal(keys[1], (windows, kv, t, d), dtype),
            jax.random.normal(keys[2], (windows, kv, t, e), dtype),
            jax.random.normal(keys[3], (kv, g), jnp.float32),
            jax.random.normal(keys[4], (windows, kv, g, t, e), jnp.float32))


@pytest.mark.parametrize("window", [4, 16])
def test_a_sink_is_one_more_column_of_the_softmax(window):
    """`plain` with a sink against the formula written out in numpy: p_ij =
    exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij')) over the keys the mask
    lets through, the sink unscaled and with no value."""
    q, k, v, sink, _ = _core_inputs(2, 3, 16)
    got = np.asarray(attention.plain(q, k, v, window, sink=sink))
    q, k, v, b = (np.asarray(a, np.float64) for a in (q, k, v, sink))
    i, j = np.arange(16)[:, None], np.arange(16)[None, :]
    seen = (j <= i) & (i - j < window)
    for w in range(2):
        for h in range(2):
            for g in range(3):
                s = q[w, h, g] @ k[w, h].T / math.sqrt(12)
                top = np.where(seen, np.exp(s), 0.0)
                p = top / (np.exp(b[h, g]) + top.sum(-1, keepdims=True))
                np.testing.assert_allclose(got[w, h, g], p @ v[w, h],
                                           atol=1e-6)


def test_a_sink_far_below_the_scores_is_no_sink_and_ones_give_its_mass():
    q, k, v, sink, _ = _core_inputs(2, 3, 16)
    none = attention.plain(q, k, v, 4)
    np.testing.assert_allclose(
        attention.plain(q, k, v, 4, sink=jnp.full_like(sink, -60.0)), none,
        atol=1e-12)
    assert float(jnp.max(jnp.abs(
        attention.plain(q, k, v, 4, sink=sink) - none))) > 0.05
    # values of ones collect what the keys keep: 1 - the sink's probability
    kept = attention.plain(q, k, jnp.ones_like(v), 4, sink=sink)
    assert float(kept.min()) > 0.0 and float(kept.max()) < 1.0
    # the first token sees itself and the sink alone
    s00 = jnp.sum(q[:, :, :, 0] * k[:, :, None, 0], -1) / math.sqrt(12)
    np.testing.assert_allclose(
        kept[..., 0, 0], jax.nn.sigmoid(s00 - sink[None]), atol=1e-6)


# -------------------------------------------------- the router, ops/moe.py


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (40, 8), jnp.float32)
    router = jax.random.normal(keys[1], (8, 16), jnp.float32)
    bias = 0.3 * jax.random.normal(keys[2], (16,), jnp.float32)
    experts, coef, chosen_by = moe.route(x, router, 3, 1.0, bias=bias)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(
        router, np.float64)))
    np.testing.assert_allclose(chosen_by, s + np.asarray(bias), atol=1e-5)
    want = np.argsort(-(s + np.asarray(bias, np.float64)), -1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    np.testing.assert_allclose(coef, picked / picked.sum(-1, keepdims=True),
                               atol=1e-5)
    np.testing.assert_allclose(np.sum(coef, -1), 1.0, atol=1e-5)
    # the bias moves the choice and never the weight of what both choose
    plain, coef0, _ = moe.route(x, router, 3, 1.0, bias=jnp.zeros(16))
    assert (np.sort(plain, -1) != np.sort(experts, -1)).any()
    raw = moe.route(x, router, 3, 2.0, bias=bias, renormalise=False)[1]
    np.testing.assert_allclose(raw, 2.0 * picked, atol=1e-5)
    with pytest.raises(ValueError, match="one group"):
        moe.route(x, router, 3, 1.0, groups=4, groups_kept=2, bias=bias)


def test_a_router_without_a_bias_is_the_softmax_it_was():
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (10, 8), jnp.float32)
    router = jax.random.normal(keys[1], (8, 16), jnp.float32)
    experts, coef, probs = moe.route(x, router, 3, 2.5)
    want = jax.nn.softmax(x @ router, -1)
    np.testing.assert_allclose(probs, want, atol=1e-6)
    top = np.sort(np.asarray(want), -1)[:, -3:]
    np.testing.assert_allclose(np.sort(coef, -1),
                               2.5 * top / top.sum(-1, keepdims=True),
                               atol=1e-5)
    assert experts.dtype == jnp.int32


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    """float32 against float64 on the same weights: 5e-5 absolute on logits
    of magnitude 3.5 (read at 1.1e-6)."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:windows])
    got = model.apply_flat(w, tokens, frozen)
    want, _ = _ref64()[1](frozen, w, tokens)
    assert got.shape == (windows, 16, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("windows", [1, 3])
def test_loss_matches_the_reference(tiny, windows):
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    spec = published(TINY)
    want = jax.jit(lambda frozen, w, tokens, labels: ref.loss(
        spec, frozen, ref.unflatten(spec, w, jnp.float64), tokens, labels,
        jnp.float64))(frozen, w, tokens, labels)
    np.testing.assert_allclose(model.loss_flat(w, tokens, labels, frozen),
                               want, rtol=1e-5)


@pytest.mark.parametrize("windows", [1, 2])
def test_every_adapter_gradient_matches_the_reference(tiny, windows):
    """Through the sink's softmax, both masks, the fused product's split,
    the partial rotary and the dispatch, against `jax.grad` of the dense
    reference: float32's rounding on gradients up to 0.13 (read at
    1.1e-7), relative to each leaf's largest."""
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
    assert names[:4] == ["layers[0].o", "layers[0].qkv", "layers[1].o",
                         "layers[1].qkv"]
    assert ref.kinds(published(TINY)) == [TINY.kind(at) for at in range(5)] \
        == [("full", False), ("window", True), ("window", True),
            ("full", True), ("window", True)]
    for name, piece in ref.leaves(published(TINY), np.asarray(w)):
        layer, leaf = name.split(".")
        mine = tree["layers"][int(layer[len("layers["):-1])][leaf]
        np.testing.assert_array_equal(np.ravel(mine), piece, err_msg=name)


def test_the_router_picks_what_the_reference_picks(tiny):
    """All 16 experts scored, three a token, at each of the four sparse
    layers; what they are chosen by (s + b) agrees, and the chosen sets
    wherever the reference's third and fourth are not within float32's
    rounding; the coefficients are the reference's s_e / sum s."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    experts, chosen_by = mimo_v2.routing(TINY, model.unravel(w), tokens,
                                         frozen)
    _, picks = _ref64()[1](frozen, w, tokens)
    assert experts.shape == (4, 32, 3) and chosen_by.shape == (4, 32, 16)
    assert len(picks) == 4
    for at, (want_i, want_by) in enumerate(picks):
        np.testing.assert_allclose(chosen_by[at], want_by, atol=1e-5)
        ordered = np.sort(np.asarray(want_by), -1)
        clear = ordered[:, -3] - ordered[:, -4] > 1e-4
        assert clear.sum() > 20
        np.testing.assert_array_equal(
            np.sort(np.asarray(experts[at]), -1)[clear],
            np.sort(np.asarray(want_i), -1)[clear])


def test_the_head_is_untied_and_the_sinks_are_the_window_layers(tiny):
    model, frozen, w, x, _ = tiny
    assert frozen["head"].shape == (32, 64)
    assert frozen["embed"].shape == (64, 32)
    for at, layer in enumerate(frozen["layers"]):
        assert ("sink" in layer) == (TINY.pattern[at] == 1), at
        assert ("dense" in layer) == (at == 0)
        assert ("router_bias" in layer) == (at > 0)
        assert "shared" not in layer
    assert frozen["layers"][1]["sink"].shape == (4,)
    assert frozen["layers"][0]["w_qkv"].shape == (32, 4 * 12 + 12 + 8)
    assert frozen["layers"][1]["w_qkv"].shape == (32, 4 * 12 + 2 * 12 + 2 * 8)
    assert frozen["layers"][1]["wo"].shape == (4 * 8, 32)
    assert TINY.rotary == 4  # int(0.334 x 12)
    assert mimo_v2.PRESETS["mimo_v2_fedlora"].rotary == 64


def test_the_four_shares_add_up_to_the_uncut_layer(tiny):
    """The model's own sparse layer (a window one and a full one) on each
    of four chips' 4 of the 16 experts, against the reference's UNCUT
    layer: four shares' results less three times what every chip computes
    alike (the residual and the attention block). No shared expert: the
    held experts' part is the whole MLP."""
    model, frozen, w, x, _ = tiny
    spec = published(TINY)
    key = jax.random.PRNGKey(5)
    h = frozen["embed"][jnp.asarray(x[:2])][None]         # [1, 2, T, H]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))
    for layer in (1, 3):
        full = dict(frozen["layers"][layer])
        full["experts"] = {
            name: jax.random.normal(jax.random.fold_in(key, i),
                                    (16,) + leaf.shape[1:], jnp.float32) / 5
            for i, (name, leaf) in enumerate(
                sorted(full["experts"].items()))}
        lora64 = ref.unflatten(spec, w, jnp.float64)[layer]
        h64 = jnp.asarray(h[0], jnp.float64)
        uncut, _ = ref.layer(spec, layer, h64, full, lora64, jnp.float64, {})
        none = dict(full, experts=jax.tree.map(lambda a: a[:0],
                                               full["experts"]))
        alike, _ = ref.layer(spec, layer, h64, none, lora64, jnp.float64, {})
        total, held = 0.0, 0
        for share in range(4):
            cfg = dataclasses.replace(TINY, first_expert=4 * share)
            mine = dict(full, experts=jax.tree.map(
                lambda a: a[4 * share:4 * share + 4], full["experts"]))
            out, counts, _ = mimo_v2._layer(cfg, layer, h, mine,
                                            adapters["layers"][layer])
            total = total + np.asarray(out[0], np.float64)
            held += int(counts["load"].sum())
            assert int(counts["dropped"]) == 0
        assert held == 2 * 16 * TINY.top_k  # every assignment, once
        assert float(jnp.max(jnp.abs(uncut - alike))) > 0.05
        np.testing.assert_allclose(total - 3 * np.asarray(alike), uncut,
                                   atol=2e-4, err_msg=str(layer))


# (the reference's departure, the least it must move the logits by,
# relative; read at 0.45, 0.44, 0.28, 0.24, 0.31, 0.0017, 0.25, 0.34, 0.17,
# 0.17, 0.35: two frequencies over 16 positions hardly tell the two bases
# apart; at the published 32 over 2,048 they do: PERF.md section 2)
DEPARTURES = [
    ("no_sink", {"sink": False}, 0.1),
    ("sink_on_full", {"sink_on_full": True}, 0.1),
    ("no_window", {"window": False}, 0.05),
    ("window_256", {"window": 8}, 0.05),  # twice the tiny window
    ("rotary_full", {"rotary": "full"}, 0.05),
    ("one_theta", {"theta": "one"}, 5e-4),
    ("no_value_scale", {"value_scale": False}, 0.05),
    ("kv_heads_swapped", {"kv_swapped": True}, 0.05),
    ("softmax_router", {"router": "softmax"}, 0.03),
    ("no_choice_bias", {"choice_bias": False}, 0.03),
    ("no_renormalise", {"renormalise": False}, 0.05),
]


@pytest.mark.parametrize("name,variant,least",
                         DEPARTURES, ids=[d[0] for d in DEPARTURES])
def test_every_departure_of_the_reference_moves_the_logits(tiny, name,
                                                           variant, least):
    """The program sits on the reference (1e-5, relative) and every
    control's departure far from both: the sink and where it is, the
    window and its length, the partial rotary and its two bases, the value
    scale, which key/value head a query head reads, the sigmoid, the
    choice bias and the renormalised weights are in the program."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    want = np.asarray(_ref64()[1](frozen, w, tokens)[0])
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    other = np.asarray(_ref64(variant)[1](frozen, w, tokens)[0])
    scale = np.linalg.norm(want)
    assert np.linalg.norm(got - want) / scale < 1e-5
    assert np.linalg.norm(other - want) / scale > least, name


def test_the_published_dtype_runs_close_to_the_reference():
    """bfloat16 base and operands, float32 accumulation (the published
    size's arithmetic, here at the tiny widths): within bfloat16's
    resolution of the float64 reference on the same rounded weights
    wherever no router flipped."""
    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    model = mimo_v2.mimo_v2_model("mimo_v2_tiny_bf16", cfg, 16)
    frozen = model.frozen(jax.random.PRNGKey(1))
    assert frozen["layers"][1]["sink"].dtype == jnp.bfloat16
    w = model.flat_init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(ds.load_shard(DATASET, f"{DATASET}0")["x_train"][:2])
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    want = np.asarray(_ref64()[1](frozen, w, tokens)[0])
    gap = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert 1e-4 < np.median(gap) < 3e-2, np.median(gap)


def test_the_frozen_scalars_follow_their_laws():
    """The sinks around log(window) (a head's sink weighs about what its
    window's keys weigh together), the choice bias N(0, 0.05^2)."""
    big = mimo_v2.mimo_v2_model("wide", dataclasses.replace(
        TINY, heads=512, kv_heads=(1, 2), num_experts=1024), 16)
    layers = big.frozen(jax.random.PRNGKey(7))["layers"]
    sink = np.asarray(layers[1]["sink"], np.float64)
    assert sink.shape == (512,)
    assert abs(sink.mean() - math.log(4)) < 0.15 and 0.85 < sink.std() < 1.15
    bias = np.asarray(layers[1]["router_bias"], np.float64)
    assert abs(bias.mean()) < 0.01 and 0.045 < bias.std() < 0.055
    law = mimo_v2.sink_law(128)(jax.random.PRNGKey(0), (4096,))
    assert abs(float(law.mean()) - math.log(128)) < 0.1


def test_the_sinks_mass_is_what_the_dense_softmax_gives_it(tiny):
    """`model.info["sink_mass"]`: the mean probability of the sink's
    column, against the reference's dense scores of the three window
    layers recomputed here in numpy from the program's own operands."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    got = float(model.info["sink_mass"](model.unravel(w), tokens, frozen))
    assert 0.3 < got < 0.8  # N(log 4, 1) against four keys
    masses = []
    h = frozen["embed"][tokens][None]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))
    for at in range(TINY.layers):
        layer = frozen["layers"][at]
        if TINY.pattern[at]:
            q, k, v = mimo_v2._operands(TINY, "window", h, layer,
                                        adapters["layers"][at])
            kept = attention.plain(q, k, jnp.ones_like(v), TINY.window,
                                   sink=layer["sink"].reshape(2, 2))
            masses.append(1.0 - float(jnp.mean(kept)))
        h = mimo_v2._layer(TINY, at, h, layer, adapters["layers"][at])[0]
    assert got == pytest.approx(np.mean(masses), abs=1e-6)


@pytest.mark.parametrize("case", ["published", "tiny"])
def test_a_model_says_how_its_cores_are_built(case):
    """From the shapes alone: at the published widths on 2,048 tokens both
    kinds' core is the kernel, a key/value head's 16 (full) or 8 (window)
    query heads ONE at a time at blocks of 256 x 256 (the whole group fits
    no block; of the sub-groups that fit, a head alone takes the block
    that stands first in `BLOCKS`): 36 of 64 pairs under the causal mask,
    15 under the window of 128, whose mask lets a QUARTER of the visited
    scores through (0.89 of the causal ones)."""
    if case == "tiny":
        plan = mimo_v2.attention_plan(TINY, 16)
        assert plan["fused"] == 0 and plan["block_share"] == 1.0
        assert plan["kinds"]["window"]["group"] == 2
        assert plan["kinds"]["full"]["blocks"] == ()
        return
    cfg = mimo_v2.PRESETS["mimo_v2_fedlora"]
    plan = mimo_v2.attention_plan(cfg, 2048)
    assert plan["fused"] == 1
    full, window = plan["kinds"]["full"], plan["kinds"]["window"]
    assert full["group"] == window["group"] == 1
    assert full["blocks"] == window["blocks"] == (256, 256)
    assert full["block_share"] == 36 / 64
    assert window["block_share"] == 15 / 64
    assert window["seen_share"] == pytest.approx(0.2584, abs=0.001)
    assert full["seen_share"] == pytest.approx(0.8893, abs=0.001)
    assert plan["block_share"] == pytest.approx(
        (2 * 36 + 5 * 15) / (7 * 64))
    # at the siblings' 1,024 tokens the whole groups fit, at 2,048 not
    assert attention.blocks(16, 1024, 192, "bfloat16", 128) == (128, 128)
    assert attention.blocks(8, 2048, 192, "bfloat16", 128) is None
    assert [attention.blocks(each, 2048, 192, "bfloat16", 128)
            for each in (4, 2, 1)] == [(128, 128), (256, 128), (256, 256)]
    assert attention.group_split(8, 2048, 192, "bfloat16", 128) == 8
    assert attention.group_split(16, 2048, 192, "bfloat16", 128) == 16
    # a group that fits whole is never split, whatever a head alone takes
    assert attention.group_split(6, 1024, 128, "bfloat16") == 1  # Laguna's
    assert attention.group_split(8, 1024, 256, "bfloat16") == 1  # Qwen3-N.
    assert attention.group_split(4, 16, 12, "float32", 8) is None


# ------------------- the kernel with a sink, ops/attention.py (interpret)
# (at the END of the file, the heaviest: PR 39's lesson)


def _value_and_gradients(form, q, k, v, sink, cot):
    return jax.value_and_grad(
        lambda q, k, v, sink: jnp.sum(form(q, k, v, sink) * cot),
        argnums=(0, 1, 2, 3))(q, k, v, sink)


@pytest.mark.parametrize("kv,g,window", [(1, 8, 128), (1, 16, 256),
                                         (2, 4, 128)])
def test_the_kernel_with_a_sink_is_plain_with_a_sink(kv, g, window):
    """`attention.fused` in interpret mode at heads of 192 | 128 with eight
    and sixteen query heads a key/value head, under a window of 128 and
    under the causal mask (a window of T), float32: the values, dq, dk, dv
    AND the sinks' own cotangent against the `einsum` form's; and far from
    the call without a sink."""
    q, k, v, sink, cot = _core_inputs(kv, g, 256, 192, 128, 1, jnp.float32)
    got = _value_and_gradients(
        lambda q, k, v, s: attention.fused(q, k, v, window, (128, 128), None,
                                           None, s), q, k, v, sink, cot)
    want = _value_and_gradients(
        lambda q, k, v, s: attention.plain(q, k, v, window, sink=s),
        q, k, v, sink, cot)
    assert float(got[0]) == pytest.approx(float(want[0]), abs=2e-3)
    for name, a, b in zip("dq dk dv dsink".split(), got[1], want[1]):
        assert float(jnp.max(jnp.abs(b))) > 1e-3, name
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    bare = attention.fused(q, k, v, window, (128, 128))
    with_sink = attention.fused(q, k, v, window, (128, 128), None, None, sink)
    assert float(jnp.max(jnp.abs(bare - with_sink))) > 0.01


def test_the_kernel_rounds_as_plain_does_at_the_published_type():
    """bfloat16 operands at 192 | 128, G = 8, a window of 128 and a sink:
    the kernel against `plain` on the same rounded operands."""
    q, k, v, sink, cot = _core_inputs(1, 8, 256, 192, 128, 1, jnp.bfloat16)
    got = attention.fused(q, k, v, 128, (128, 128), None, None, sink)
    want = attention.plain(q, k, v, 128, sink=sink)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-2)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 5e-3


def test_a_group_too_wide_for_the_kernel_goes_in_sub_groups(monkeypatch):
    """Where `blocks` takes no block for a key/value head's whole group,
    `attention.attention` sends the kernel sub-groups, each with its own
    copy of the key/value head, and the copies' cotangents add up: values
    and gradients are `plain`'s. Forced here at 256 tokens by a VMEM budget
    that holds four heads' blocks and not eight."""
    q, k, v, sink, cot = _core_inputs(2, 8, 256, 192, 128, 1, jnp.float32)
    need = attention._buffers(4, 256, 192, 128, 128, 4, 128)
    monkeypatch.setattr(attention, "_VMEM_BUFFERS", need)
    assert attention.blocks(8, 256, 192, jnp.float32, 128) is None
    assert attention.blocks(4, 256, 192, jnp.float32, 128) == (128, 128)
    assert attention.group_split(8, 256, 192, jnp.float32, 128) in (2, 4, 8)
    got = _value_and_gradients(
        lambda q, k, v, s: attention.attention(q, k, v, 128, sink=s),
        q, k, v, sink, cot)
    want = _value_and_gradients(
        lambda q, k, v, s: attention.plain(q, k, v, 128, sink=s),
        q, k, v, sink, cot)
    assert float(got[0]) == pytest.approx(float(want[0]), abs=2e-3)
    for name, a, b in zip("dq dk dv dsink".split(), got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
