"""Qwen3-Next-80B-A3B-Instruct's gated delta-net / gated attention hybrid
with adapters (models/qwen3_next.py, ops/delta_rule.py, ops/attention.py,
ops/moe.py) against the plain float64 reference
(benchmark/reference/qwen3_next.py: the delta rule a token at a time), at
the tiny preset: two periods of three delta-net layers and an attention
layer, four chunks a 16-token window, two value heads a key head, a head
group of 2, 4 of 16 experts held, an untied head.

(Named `test_v4_...` so that it is collected LAST: the driver's workers
take files in alphabetical order, and a new heavy file in the middle moves
the neighbours of tests/test_runtime.py's live clusters; PR 31's lesson,
.claude/skills/verify/SKILL.md.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import granite_hybrid, lm, qwen3_next
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import delta_rule

from lm_family import walked_names
from test_v4_delta_rule import _rule_inputs

DATASET = "lm_tokens_tiny"
NAME = "qwen3_next_tiny"
TINY = qwen3_next.PRESETS[NAME]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "full_attention_interval": cfg.full_attention_interval,
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.rotary_factor,
        "rope_theta": cfg.rope_theta,
        "linear_num_key_heads": cfg.key_heads,
        "linear_num_value_heads": cfg.value_heads,
        "linear_key_head_dim": cfg.key_dim,
        "linear_value_head_dim": cfg.value_dim,
        "linear_conv_kernel_dim": cfg.conv,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "rms_norm_eps": cfg.eps, "first_expert": cfg.first_expert,
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


# ---------------------- the rule in the model (the rule alone:
# tests/test_v4_delta_rule.py)


def test_a_window_that_is_no_whole_number_of_chunks_is_refused():
    with pytest.raises(ValueError, match="whole number"):
        delta_rule.chunks(24, 16)
    assert delta_rule.chunks(16, 64) == 1  # shorter than a chunk: one
    with pytest.raises(ValueError, match="whole number"):
        qwen3_next.qwen3_next_model("a", TINY, 18)
    with pytest.raises(ValueError, match="value heads"):
        delta_rule.chunked(*_rule_inputs(groups=3, each=1)[:2],
                           *_rule_inputs(groups=2, each=2)[2:], 4)


# ------------------------------------- what the two hybrids' mixers share


def test_the_conv_without_a_bias_is_granites_with_none():
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(keys[0], (2, 9, 5), jnp.float32)
    weight = jax.random.normal(keys[1], (4, 5), jnp.float32)
    got = lm.causal_conv(x, weight)
    np.testing.assert_allclose(
        got, granite_hybrid.causal_conv(x, weight, jnp.zeros((5,))),
        atol=0)
    np.testing.assert_allclose(got[:, 0], weight[3] * x[:, 0], atol=1e-6)
    assert granite_hybrid.causal_conv is lm.causal_conv
    assert granite_hybrid.gated_norm is lm.gated_norm


@pytest.mark.parametrize("gate_first", [True, False])
def test_the_gated_norm_in_either_order(gate_first):
    """Granite's gate-then-norm and this model's norm-then-gate from one
    function, each against its formula written out; the two differ."""
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    y, z = (jax.random.normal(k, (3, 4, 8), jnp.float64) for k in keys[:2])
    weight = 1.0 + 0.1 * jax.random.normal(keys[2], (8,), jnp.float64)
    silu = z / (1.0 + jnp.exp(-z))

    def normed(u):
        return weight * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                                     + 1e-6)

    want = normed(y * silu) if gate_first else normed(y) * silu
    got = lm.gated_norm(y, z, weight, 1e-6, gate_first=gate_first)
    np.testing.assert_allclose(got, want, atol=1e-6)
    other = lm.gated_norm(y, z, weight, 1e-6, gate_first=not gate_first)
    assert float(jnp.max(jnp.abs(other - want))) > 0.1
    if gate_first:  # the default is Granite's
        np.testing.assert_array_equal(lm.gated_norm(y, z, weight, 1e-6), got)


def test_a_zero_centred_norm_reads_its_weight_as_one_plus():
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 8), jnp.float32)
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (8,), jnp.float32)
    np.testing.assert_allclose(lm.rms(x, w, 1e-6, zero_centred=True),
                               lm.rms(x, 1.0 + w, 1e-6), atol=1e-6)
    assert float(jnp.max(jnp.abs(lm.rms(x, w, 1e-6)))) < 1.0


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    """float32 against float64 on the same weights: 5e-5 absolute on logits
    of magnitude 4 (read at 1.5e-5)."""
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
    """Through the chunked rule's backward (its solve's included), the
    conv's, the gated norm's, the attention's and the dispatch's, against
    `jax.grad` of the token-by-token reference: float32's rounding on
    gradients up to 1.3 (read at 1.4e-5), relative to each leaf's
    largest."""
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
    assert names[6:10] == [f"layers[3].{n}" for n in "koqv"]
    assert ref.kinds(published(TINY)) == list(TINY.layer_types) \
        == (["gdn"] * 3 + ["attention"]) * 2
    for name, piece in ref.leaves(published(TINY), np.asarray(w)):
        layer, leaf = name.split(".")
        mine = tree["layers"][int(layer[len("layers["):-1])][leaf]
        np.testing.assert_array_equal(np.ravel(mine), piece, err_msg=name)


def test_the_router_picks_what_the_reference_picks(tiny):
    """All 16 experts scored, three a token, at every one of the eight
    layers; the chosen sets agree wherever the reference's third and
    fourth probabilities are not within float32's rounding."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    experts, probs = qwen3_next.routing(TINY, model.unravel(w), tokens,
                                        frozen)
    _, picks = _ref64()[1](frozen, w, tokens)
    assert experts.shape == (8, 32, 3) and probs.shape == (8, 32, 16)
    for at, (want_i, want_p) in enumerate(picks):
        np.testing.assert_allclose(probs[at], want_p, atol=1e-5)
        ordered = np.sort(np.asarray(want_p), -1)
        clear = ordered[:, -3] - ordered[:, -4] > 1e-4
        np.testing.assert_array_equal(
            np.sort(np.asarray(experts[at]), -1)[clear],
            np.sort(np.asarray(want_i), -1)[clear])


def test_the_head_is_untied_and_the_last_norm_zero_centred(tiny):
    model, frozen, w, x, _ = tiny
    assert frozen["head"].shape == (32, 64)
    assert frozen["embed"].shape == (64, 32)
    assert abs(float(jnp.mean(frozen["final_norm"]))) < 0.1  # around ZERO
    tokens = jnp.asarray(x[:1])
    h = qwen3_next.hidden_states(TINY, lm.one_peer(model.unravel(w)),
                                 tokens[None], frozen, remat=False)[0]
    want = lm.rms(h[0], 1.0 + frozen["final_norm"], TINY.eps) \
        @ frozen["head"]
    np.testing.assert_allclose(model.apply_flat(w, tokens, frozen), want,
                               atol=1e-5)


def test_the_four_shares_add_up_through_the_whole_layer(tiny):
    """The model's own layer (a delta-net one and an attention one) on
    each of four chips' 4 of the 16 experts, against the reference's UNCUT
    layer: four shares' results less three times what every chip computes
    alike (the residual, the mixer, the GATED shared expert), so the
    shared expert counts once."""
    model, frozen, w, x, _ = tiny
    spec = published(TINY)
    key = jax.random.PRNGKey(5)
    h = frozen["embed"][jnp.asarray(x[:2])][None]         # [1, 2, T, H]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))
    for layer in (1, 3):
        kind = TINY.layer_types[layer]
        full = dict(frozen["layers"][layer])
        full["experts"] = {
            name: jax.random.normal(jax.random.fold_in(key, i),
                                    (16,) + leaf.shape[1:], jnp.float32) / 5
            for i, (name, leaf) in enumerate(
                sorted(full["experts"].items()))}
        lora64 = ref.unflatten(spec, w, jnp.float64)[layer]
        h64 = jnp.asarray(h[0], jnp.float64)
        uncut, _ = ref.layer(spec, kind, h64, full, lora64, jnp.float64, {})
        none = dict(full, experts=jax.tree.map(lambda a: a[:0],
                                               full["experts"]))
        alike, _ = ref.layer(dict(spec), kind, h64, none, lora64,
                             jnp.float64, {})
        total, held = 0.0, 0
        for share in range(4):
            cfg = dataclasses.replace(TINY, first_expert=4 * share)
            mine = dict(full, experts=jax.tree.map(
                lambda a: a[4 * share:4 * share + 4], full["experts"]))
            out, counts, _ = qwen3_next._layer(cfg, layer, h, mine,
                                               adapters["layers"][layer])
            total = total + np.asarray(out[0], np.float64)
            held += int(counts["load"].sum())
            assert int(counts["dropped"]) == 0
        assert held == 2 * 16 * TINY.top_k  # every assignment, once
        np.testing.assert_allclose(total - 3 * np.asarray(alike), uncut,
                                   atol=2e-4, err_msg=kind)
        # and with the shared expert ungated the reference's is another
        bare, _ = ref.layer(spec, kind, h64, full, lora64, jnp.float64,
                            {"shared_gate": False})
        assert float(jnp.max(jnp.abs(bare - uncut))) > 1e-2


# (the reference's departure, the least it must move the logits by,
# relative; read at 0.49, 0.71, 0.0026, 0.83, 0.81, 1.07, 1.00, 0.51, 0.75,
# 0.20, 0.49)
DEPARTURES = [
    ("no_delta", {"delta": False}, 0.1),
    ("beta_one", {"beta": 1.0}, 0.1),
    ("decay_bfloat16", {"decay": "bfloat16", "chunk": 4}, 5e-4),
    ("no_carry", {"carry": False, "chunk": 4}, 0.1),
    ("no_l2norm", {"l2norm": False}, 0.1),
    ("gate_before_norm", {"gate_first": True}, 0.1),
    ("norm_not_zero_centred", {"zero_centred": False}, 0.1),
    ("no_output_gate", {"output_gate": False}, 0.1),
    ("no_shared_gate", {"shared_gate": False}, 0.1),
    ("rotary_full", {"rotary": "full"}, 0.02),
    ("no_renormalise", {"renormalise": False}, 0.1),
]


@pytest.mark.parametrize("name,variant,least",
                         DEPARTURES, ids=[d[0] for d in DEPARTURES])
def test_every_departure_of_the_reference_moves_the_logits(tiny, name,
                                                           variant, least):
    """The program sits on the reference (1e-5, relative) and every
    control's departure far from both: the correction, beta, the carried
    state, the two l2 norms, the norm's place, `1 + w`, the two gates, the
    partial rotary and the renormalised top-k are in the program."""
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
    model = qwen3_next.qwen3_next_model("qwen3_next_tiny_bf16", cfg, 16)
    frozen = model.frozen(jax.random.PRNGKey(1))
    assert frozen["layers"][0]["a_log"].dtype == jnp.bfloat16
    w = model.flat_init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(ds.load_shard(DATASET, f"{DATASET}0")["x_train"][:2])
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    want = np.asarray(_ref64()[1](frozen, w, tokens)[0])
    gap = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert 1e-4 < np.median(gap) < 3e-2, np.median(gap)


def test_the_frozen_scalars_follow_their_laws():
    layers = model_for_dataset(DATASET, NAME).frozen(
        jax.random.PRNGKey(7))["layers"]
    layer, attention = layers[0], layers[3]
    a = np.exp(np.asarray(layer["a_log"], np.float64))
    assert ((a > 0.0) & (a <= 16.0)).all()
    step = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))
    assert ((step >= 0.99e-3) & (step <= 0.101)).all()  # Mamba-2's, not 1
    assert abs(float(np.mean(layer["gate_norm"])) - 1.0) < 0.2   # w
    assert abs(float(np.mean(layer["norm"]))) < 0.1              # 1 + w
    assert layer["conv_w"].shape == (4, 2 * 2 * 8 + 4 * 8)
    assert "conv_b" not in layer and layer["w_ba"].shape == (32, 8)
    assert layer["w_qkvz"].shape == (32, 2 * 16 + 2 * 32)
    assert attention["wq"].shape == (32, 2 * 4 * 8)  # a head [q | gate]
    assert attention["q_norm"].shape == attention["k_norm"].shape == (8,)
    assert layer["shared_gate"].shape == (32, 1)


@pytest.mark.parametrize("case", ["published", "tiny"])
def test_a_model_says_which_side_of_the_rules_dispatch_it_runs(case):
    """`model.info["gdn_rule"]`: the kernel at the published shapes (heads
    of 128 | 128, chunks of 64, bfloat16), the `jax.numpy` form at the
    tiny preset's (D = 8, chunks of 4)."""
    if case == "published":
        info = qwen3_next.qwen3_next_model(
            "a", qwen3_next.PRESETS["qwen3_next_fedlora"],
            1024).info["gdn_rule"]
        assert info == {"kernel": 1, "states_saved": 1,
                        "key_heads_a_step": delta_rule.key_heads_a_step(16),
                        "value_heads_a_step": 4, "padded_share": 0.0}
        assert 16 % info["key_heads_a_step"] == 0
    else:
        info = qwen3_next.qwen3_next_model("a", TINY, 16).info["gdn_rule"]
        assert info == {"kernel": 0, "states_saved": 0,
                        "key_heads_a_step": 0, "value_heads_a_step": 0,
                        "padded_share": 0.0}


# --------------- a block's mixers, a peer at a time (PR 49; appended: the
# tests above run on the schedule they had)


def _a_block_through_a_layer(tiny, kind, peers=3):
    """(`_layer_of`'s h' and its sum against a cotangent, as a function of
    (adapters with a peer axis, h [P, 2, 16, 32], the cotangent); those
    three) at the tiny preset, float32."""
    frozen = tiny[1]["layers"][TINY.layer_types.index(kind)]
    keys = jax.random.split(jax.random.PRNGKey(49), 3)
    h = jax.random.normal(keys[0], (peers, 2, 16, TINY.hidden), jnp.float32)
    adapters = {
        name: 0.1 * jax.random.normal(jax.random.fold_in(keys[1], i),
                                      (peers, TINY.rank, out), jnp.float32)
        for i, (name, (_, out)) in enumerate(
            sorted(qwen3_next._widths(TINY, kind).items()))}
    cot = jax.random.normal(keys[2], h.shape, jnp.float32)

    def through(adapters, h, cot):
        out = qwen3_next._layer_of(TINY, kind, h, frozen, adapters)[0]
        return jnp.sum(out * cot), out

    return through, adapters, h, cot


@pytest.mark.parametrize("kind", ["gdn", "attention"])
def test_a_block_of_3_through_a_layer_is_three_blocks_of_1(tiny, kind):
    """`_layer_of` on a block of three peers (its mixer a peer at a time,
    its experts on the block's tokens as one batch)
    gives, peer for peer, the values and the adapters' gradients of three
    blocks of one peer, which walk nothing (float32, to 1e-6 of the
    largest entry)."""
    through, *block = _a_block_through_a_layer(tiny, kind)
    both = jax.value_and_grad(through, has_aux=True)
    (_, out), grads = both(*block)
    assert out.shape == block[1].shape and set(grads) == set(block[0])
    for peer in range(3):
        (_, alone), own = both(*jax.tree.map(lambda a: a[peer:peer + 1],
                                             block))
        np.testing.assert_allclose(out[peer], alone[0], atol=1e-6
                                   * float(jnp.max(jnp.abs(alone))))
        for name, mine in own.items():
            largest = float(jnp.max(jnp.abs(mine)))
            assert largest > 1e-3, name
            np.testing.assert_allclose(grads[name][peer], mine[0],
                                       atol=1e-6 * largest)


@pytest.mark.parametrize("kind", ["gdn", "attention"])
def test_a_block_of_1_walks_nothing_and_a_block_of_3_walks_the_mixer(tiny,
                                                                     kind):
    """The walk follows from the shape of `h` alone: a block of one peer
    lowers with no `peer_walk` in its text (as before the walk was there),
    a block of three compiles with the mixer's scopes under it and the
    router's and the experts' out of it."""
    mixer = ("gdn_rule", "gdn_conv", "gdn_gate", "gdn_proj") \
        if kind == "gdn" else ("attn_core", "attn_in", "attn_out")

    def lowered(peers):
        through, *block = _a_block_through_a_layer(tiny, kind, peers)
        return jax.jit(jax.grad(lambda *a: through(*a)[0])).lower(*block)

    assert "peer_walk" not in lowered(1).as_text(debug_info=True)
    walked = walked_names(lowered(3).compile().as_text())
    for scope in mixer:
        assert any(scope in name for name in walked), scope
    for scope in ("lm_experts", "lm_router", "lm_dense"):
        assert not any(scope in name for name in walked), scope


@pytest.mark.parametrize("preset,length,layers", [
    ("qwen3_next_fedlora", 1024, 9), ("qwen3_next_tiny", 16, 6)])
def test_a_model_counts_the_delta_net_layers_a_block_walks(preset, length,
                                                           layers):
    """`biscotti_gdn_walked_layers`: every delta-net layer of the preset
    (three of four), declared beside the rule's two gauges."""
    model = qwen3_next.qwen3_next_model("a", qwen3_next.PRESETS[preset],
                                        length)
    rows = {name: value for name, _, value, _ in model.info["gauges"]}
    assert rows["biscotti_gdn_walked_layers"] == layers
    assert {"biscotti_gdn_chunks", "biscotti_gdn_rule_kernel"} <= set(rows)
