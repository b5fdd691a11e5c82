"""Laguna-S-2.1's decoder share with adapters (models/laguna.py, ops/moe.py)
against the plain float64 reference (benchmark/reference/laguna.py), at the
tiny preset: every kind of layer (full + dense, sliding + sparse, full +
sparse) and the 48/72-style head split (4, 6, 4 query heads on 2 KV heads),
4 of 16 experts held, 3 a token."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import laguna
from biscotti_tpu.models.trainer import block_step_fn, local_step_fn
from biscotti_tpu.models.zoo import MODELS, model_for_dataset
from biscotti_tpu.ops import moe
from biscotti_tpu.parallel.sim import Simulator

from lm_family import (  # noqa: F401  (collected, run and counted here)
    DATASET, Family, a_block_of_peers_is_each_peer_alone, cfg_of, family,
    the_round_trains_the_adapters_and_reports, tiny,
    test_trainer_step_is_the_simulators_for_the_same_batch)

TINY = laguna.PRESETS["laguna_tiny"]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "head_dim": cfg.head_dim,
        "num_key_value_heads": cfg.kv_heads,
        "num_attention_heads_per_layer": list(cfg.heads),
        "layer_types": [kind + "_attention" for kind in cfg.layer_types],
        "mlp_only_layers": list(cfg.dense_layers),
        "sliding_window": cfg.window, "num_experts_per_tok": cfg.top_k,
        "moe_routed_scaling_factor": cfg.routed_scale,
        "rope_parameters": {
            "full_attention": dict(cfg.rope_full, rope_type="yarn"),
            "sliding_attention": dict(cfg.rope_sliding,
                                      rope_type="default")},
        "rms_norm_eps": cfg.eps, "first_expert": cfg.first_expert,
        "lora_rank": cfg.rank, "lora_alpha": cfg.alpha}


# the family's round cases (tests/lm_family.py) over this model's record
FAMILY = Family(
    module=laguna, ref=ref, name="laguna_tiny", published=published,
    num_params=608, load=(2, 4),
    clip=0.05,  # the clip bound under a gradient of norm ~0.5
    gauges=("biscotti_lm_attention_shared_key 0",  # each head's own
            "biscotti_moe_assignments_held",
            "biscotti_moe_load_max_over_mean",
            "biscotti_moe_tokens_dropped 0"))


def _ref64(cfg):
    return ref.compiled(published(cfg), jnp.float64)


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    model, frozen, w, x, _ = tiny
    _, run = _ref64(TINY)
    want, _ = run(frozen, w, jnp.asarray(x[:windows]))
    got = model.apply_flat(w, jnp.asarray(x[:windows]), frozen)
    assert got.shape == (windows, 16, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("windows", [1, 3])
def test_loss_and_error_match_the_reference(tiny, windows):
    model, frozen, w, x, y = tiny
    x, y = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    spec = published(TINY)
    want = ref.loss(spec, frozen, ref.unflatten(spec, w, jnp.float64), x, y,
                    jnp.float64)
    np.testing.assert_allclose(model.loss_flat(w, x, y, frozen), want,
                               rtol=1e-5)
    logits, _ = _ref64(TINY)[1](frozen, w, x)
    np.testing.assert_allclose(
        model.error_flat(w, x, y, frozen),
        np.mean(np.argmax(logits, -1) != np.asarray(y)), atol=1e-6)


@pytest.mark.parametrize("windows", [1, 2])
def test_flat_adapter_gradient_matches_the_reference(tiny, windows):
    model, frozen, w, x, y = tiny
    x, y = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    want = _ref64(TINY)[0](frozen, w, x, y)
    got = jax.grad(model.loss_flat)(w, x, y, frozen)
    assert got.shape == (model.num_params,) == (ref.num_params(
        published(TINY)),)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert float(jnp.linalg.norm(want)) > 1e-2  # and it is not nothing


def test_the_router_picks_what_the_reference_picks(tiny):
    model, frozen, w, x, _ = tiny
    chosen, probs = laguna.routing(TINY, model.unravel(w), jnp.asarray(x[:2]),
                                   frozen)
    _, picks = _ref64(TINY)[1](frozen, w, jnp.asarray(x[:2]))
    assert chosen.shape == (2, 32, 3) and chosen.dtype == jnp.int32
    for got, got_p, (want, want_p) in zip(chosen, probs, picks):
        np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
        np.testing.assert_allclose(got_p, want_p, atol=1e-6)


def _wide_heads(t=128):
    """The tiny preset with heads of 128 on windows of `t` tokens: shapes
    ops/attention.py's kernel takes (interpreted here), where the tiny
    preset's own stay on the `einsum` form."""
    cfg = dataclasses.replace(TINY, head_dim=128)
    model = laguna.laguna_model("laguna_wide_heads", cfg, t)
    assert model.info["attention"]["fused"] == 1
    assert not model_for_dataset(DATASET).info["attention"]["fused"]
    tokens = jax.random.randint(jax.random.PRNGKey(3), (6, t + 1), 0,
                                cfg.vocab, jnp.int32)
    return (cfg, model, model.frozen(jax.random.PRNGKey(1)),
            model.flat_init(jax.random.PRNGKey(2)), tokens[:, :-1],
            tokens[:, 1:])


@pytest.mark.parametrize("side", ["einsum", "kernel"])
def test_a_block_of_peers_is_each_peer_alone(tiny, side):
    """On the kernel's side too: its grid walks the windows."""
    a_block_of_peers_is_each_peer_alone(
        FAMILY, tiny if side == "einsum" else _wide_heads()[1:])


@pytest.mark.parametrize("name", ["laguna_tiny", "deepseek_v2_tiny"])
def test_a_blocks_attention_is_walked_a_peer_at_a_time(name):
    """A block of peers is there for the routed experts: the attention of
    a block of 3 is a loop over its peers (`lm.peer_at_a_time`), a peer's
    alone is the program it was (no loop), and the block's hidden states
    and every adapter's gradient are its peers' own, each computed alone,
    where no token of one peer meets another's in the experts
    (they share the sorted buffer, not a row of it)."""
    from biscotti_tpu.models import deepseek_v2

    model = model_for_dataset(DATASET, name)
    module = {"laguna_tiny": laguna, "deepseek_v2_tiny": deepseek_v2}[name]
    cfg = model.info["config"]
    frozen = model.frozen(jax.random.PRNGKey(1))
    one = model.init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    tokens = jnp.asarray(shard["x_train"][:6]).reshape(3, 2, -1)
    params = jax.tree.map(lambda b: jnp.stack([b, 2.0 * b, -b]), one)

    def loss(params, tokens):
        h, _, _ = module.hidden_states(cfg, params, tokens, frozen)
        return jnp.sum(h * h), h

    def loops(peers):
        some = jax.tree.map(lambda b: b[:peers], params)
        return str(jax.make_jaxpr(lambda p: loss(p, tokens[:peers])[0])(
            some)).count("scan[")

    assert loops(1) == 0 and loops(3) >= 1
    grad = jax.jit(jax.value_and_grad(loss, has_aux=True))
    (_, h), grads = grad(params, tokens)
    for peer in (0, 2):
        own = jax.tree.map(lambda b: b[peer:peer + 1], params)
        (_, want), want_grads = grad(own, tokens[peer:peer + 1])
        got = (h[peer:peer + 1],
               jax.tree.map(lambda g: g[peer:peer + 1], grads))
        for a, b in zip(jax.tree.leaves(got),
                        jax.tree.leaves((want, want_grads))):
            np.testing.assert_allclose(
                a, b, atol=1e-5 * float(jnp.max(jnp.abs(b))))


# ------------------------------------------------------ the expert layer


def _layer_inputs(key, n=24):
    hidden, width, experts = TINY.hidden, TINY.expert_width, TINY.num_experts
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, hidden), jnp.float32)
    router = jax.random.normal(ks[1], (hidden, experts)) / np.sqrt(hidden)
    weights = {
        "w_gate": jax.random.normal(ks[2], (experts, hidden, width)) / 6,
        "w_up": jax.random.normal(ks[3], (experts, hidden, width)) / 6,
        "w_down": jax.random.normal(ks[4], (experts, width, hidden)) / 3}
    return x, router.astype(jnp.float32), jax.tree.map(
        lambda a: a.astype(jnp.float32), weights)


def _dense_experts(x, experts, coef, weights):
    """Every expert on every token, weighed by the token's coefficient."""
    x, coef = np.asarray(x, np.float64), np.asarray(coef, np.float64)
    out = np.zeros_like(x)
    for e in range(weights["w_gate"].shape[0]):
        mine = np.where(np.asarray(experts) == e, coef, 0.0).sum(-1)
        g = x @ np.asarray(weights["w_gate"][e], np.float64)
        u = x @ np.asarray(weights["w_up"][e], np.float64)
        out += mine[:, None] * ((g / (1 + np.exp(-g)) * u)
                                @ np.asarray(weights["w_down"][e],
                                             np.float64))
    return out


def test_the_four_shares_add_up_to_the_uncut_layer():
    """What each of four chips computes for its own 4 of the 16 experts,
    added up, is the whole routed sum (the shared expert is every chip's
    alike and counted once, outside)."""
    x, router, weights = _layer_inputs(jax.random.PRNGKey(3))
    experts, coef, _ = moe.route(x, router, TINY.top_k, TINY.routed_scale)
    parts, held = [], 0
    for share in range(4):
        mine = jax.tree.map(lambda a: a[4 * share:4 * share + 4], weights)
        part, counts = moe.held_experts(x, experts, coef, mine,
                                        first=4 * share, total=16)
        parts.append(np.asarray(part, np.float64))
        held += int(counts["load"].sum())
        assert int(counts["dropped"]) == 0
    assert held == x.shape[0] * TINY.top_k  # every assignment, once
    np.testing.assert_allclose(sum(parts),
                               _dense_experts(x, experts, coef, weights),
                               atol=1e-5)
    np.testing.assert_allclose(jnp.sum(coef, -1), TINY.routed_scale,
                               rtol=1e-6)


def test_the_shares_add_up_through_the_whole_layer(tiny):
    """The same through the model's own layer against the reference's
    UNCUT layer: four shares' results less three times what every chip
    computes alike (the residual, the attention, the shared expert)."""
    model, frozen, w, x, _ = tiny
    spec, at = published(TINY), 1
    key = jax.random.PRNGKey(5)
    full = dict(frozen["layers"][at])
    full["experts"] = {
        name: jax.random.normal(jax.random.fold_in(key, i),
                                (16,) + leaf.shape[1:], jnp.float32) / 5
        for i, (name, leaf) in enumerate(sorted(full["experts"].items()))}
    h = frozen["embed"][jnp.asarray(x[:2])][None]         # [1, 2, T, H]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))
    lora64 = ref.unflatten(spec, w, jnp.float64)[at]
    uncut, _ = ref.layer(spec, at, jnp.asarray(h[0], jnp.float64), full,
                         lora64, jnp.float64, {})
    none = dict(full, experts=jax.tree.map(lambda a: a[:0], full["experts"]))
    alike, _ = ref.layer(spec, at, jnp.asarray(h[0], jnp.float64), none,
                         lora64, jnp.float64, {})
    total = 0.0
    for share in range(4):
        cfg = dataclasses.replace(TINY, first_expert=4 * share)
        mine = dict(full, experts=jax.tree.map(
            lambda a: a[4 * share:4 * share + 4], full["experts"]))
        out, _, _ = laguna._layer(cfg, at, h, mine, adapters["layers"][at])
        total = total + np.asarray(out[0], np.float64)
    np.testing.assert_allclose(total - 3 * np.asarray(alike), uncut,
                               atol=5e-5)


@pytest.mark.parametrize("total", [0, 16])
def test_no_token_is_dropped_when_most_go_to_one_expert(total):
    """`total` 16: the sorted buffer is cut to twice a uniform router's
    rows, 60 of 120, the crowd passes it, and the uncut path takes over."""
    x, router, weights = _layer_inputs(jax.random.PRNGKey(4), n=40)
    held = jax.tree.map(lambda a: a[:4], weights)
    experts, coef, _ = moe.route(x, router, TINY.top_k, TINY.routed_scale)
    # 36 of 40 tokens put expert 2 first; the rest keep their own choices
    crowd = experts.at[:36, 0].set(2)
    crowd = jnp.where((crowd[:, 1:] == 2).any(-1, keepdims=True)
                      & (jnp.arange(40) < 36)[:, None],
                      jnp.stack([crowd[:, 0], crowd[:, 0] + 5,
                                 crowd[:, 0] + 6], -1), crowd)
    crowd = crowd.at[:30, 1].set(1)  # and a second held expert for most
    out, counts = jax.jit(lambda *a: moe.held_experts(
        *a, first=0, total=total))(x, crowd, coef, held)
    assert int(counts["load"].sum()) > 60
    assert int(counts["load"][2]) == int(jnp.sum(crowd == 2)) >= 36
    assert int(counts["dropped"]) == 0
    assert int(counts["load"].sum()) == int(jnp.sum(crowd < 4))
    np.testing.assert_allclose(out, _dense_experts(x, crowd, coef, held),
                               atol=1e-5)


def test_nothing_held_here_gives_nothing():
    x, router, weights = _layer_inputs(jax.random.PRNGKey(6))
    experts, coef, _ = moe.route(x, router, TINY.top_k, TINY.routed_scale)
    held = jax.tree.map(lambda a: a[:4], weights)
    out, counts = moe.held_experts(x, experts, coef, held, first=100)
    assert int(counts["load"].sum()) == 0 and not np.asarray(out).any()


# --------------------------------------------------------- the attention


@pytest.mark.parametrize("side,window", [("einsum", 4), ("einsum", 16),
                                         ("kernel", 40), ("kernel", 128)])
def test_the_sliding_mask_differs_from_the_causal_one_beyond_the_window(
        tiny, side, window):
    if side == "einsum":
        (model, frozen, w, x, _), cfg = tiny, TINY
    else:
        cfg, model, frozen, w, x, _ = _wide_heads()
    at = 1  # the sliding layer
    h = frozen["embed"][jnp.asarray(x[:1])][None]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))["layers"][at]
    sliding = laguna._attention(dataclasses.replace(cfg, window=window), at,
                                h, frozen["layers"][at], adapters)[0, 0]
    causal = laguna._attention(dataclasses.replace(cfg, window=10**6), at,
                               h, frozen["layers"][at], adapters)[0, 0]
    same = np.isclose(sliding, causal, atol=1e-6).all(axis=-1)   # [T]
    assert same[:window].all()            # key j is seen iff 0 <= i - j < W
    assert not same[window:].any()


def test_full_layers_rotate_half_the_head_with_yarn_and_sliding_all_of_it():
    cos_f, sin_f, rot_f = laguna.rotary_tables(TINY, "full", 16)
    cos_s, sin_s, rot_s = laguna.rotary_tables(TINY, "sliding", 16)
    assert (rot_f, rot_s) == (4, 8) and cos_f.shape == (16, 2)
    np.testing.assert_allclose(cos_s[0], 1.0)
    np.testing.assert_allclose(cos_f[0], TINY.rope_full["attention_factor"])
    spec = published(TINY)
    for kind, got in (("full_attention", (cos_f, sin_f)),
                      ("sliding_attention", (cos_s, sin_s))):
        want = ref.rotary(spec, kind, 16)
        np.testing.assert_allclose(got[0], want[0], atol=1e-6)
        np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    big = laguna.PRESETS["laguna_s_fedlora"]
    cos, _, rot = laguna.rotary_tables(big, "full", 1024)
    assert rot == 64 and cos.shape == (1024, 32)


# ------------------------------------------- what is trained and what not


def _all_shapes(jaxpr, found):
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if hasattr(aval, "shape"):
                found.append((eqn.primitive.name, tuple(aval.shape),
                              str(aval.dtype)))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _all_shapes(sub, found)
    return found


def test_no_gradient_of_a_frozen_leaf_is_formed(tiny):
    """The committed d is the adapters'; no product in the step's program
    (a weight's gradient is one) makes an array of an expert stack's, the
    embedding's, the head's or the dense MLP's shape."""
    model, frozen, w, x, y = tiny
    assert model.num_params == sum(
        TINY.rank * (n * TINY.head_dim + 2 * TINY.kv_heads * TINY.head_dim
                     + TINY.hidden) for n in TINY.heads) == 608
    step = block_step_fn(model, "clipped_sgd", 1.0, 0.1)
    # three peers: 48 tokens, a count that is no width of the tiny model
    xb, yb = jnp.asarray(x[:3])[:, None], jnp.asarray(y[:3])[:, None]
    made = _all_shapes(jax.make_jaxpr(step)(w, xb, yb, frozen).jaxpr, [])
    assert any("ragged_dot" in name for name, _, _ in made)
    shapes = {shape for name, shape, _ in made if "dot" in name}
    experts = frozen["layers"][1]["experts"]
    for leaf in (experts["w_gate"], experts["w_down"], frozen["embed"],
                 frozen["head"], frozen["layers"][0]["dense"]["w_gate"]):
        assert tuple(leaf.shape) not in shapes, leaf.shape
    # the three peers' gradients
    assert (3, model.num_params) in {shape for _, shape, _ in made}


def test_no_gradient_of_a_frozen_leaf_is_formed_where_the_kernel_runs(tiny):
    """The same claim at a layer ops/grouped_matmul.py takes (hidden size
    and expert width 128; four peers' 64 tokens, four experts a token: a
    cut buffer of 128 rows, an uncut one of 256): the grouped products are
    `pallas_call`s, and no equation of the step's program that COMPUTES
    (theirs included; a transposed copy of the weights, the compiler's
    `ragged_dot` backward, is one) puts out an array of the expert stack's
    shape. What does is JAX's plumbing: the weights handed from the forward
    `cond` (and the `jit` of `held_experts` around it) to the backward one
    as residuals, and the zeros a `custom_vjp` rule's `None` stands for,
    read by nothing (the compiled program has none of them:
    tests/test_tpu_lowering.py)."""
    _, _, _, x, y = tiny
    cfg = dataclasses.replace(TINY, hidden=128, expert_width=128, top_k=4)
    model = laguna.laguna_model("laguna_wide", cfg, x.shape[-1])
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    step = block_step_fn(model, "clipped_sgd", 1.0, 0.1)
    xb, yb = jnp.asarray(x[:4])[:, None], jnp.asarray(y[:4])[:, None]
    made = _all_shapes(jax.make_jaxpr(step)(w, xb, yb, frozen).jaxpr, [])
    names = {name for name, _, _ in made}
    assert "pallas_call" in names and "ragged_dot" not in names
    experts = frozen["layers"][1]["experts"]
    assert experts["w_gate"].shape == (4, 128, 128) == experts["w_down"].shape
    assert {name for name, shape, _ in made if shape == (4, 128, 128)} \
        <= {"cond", "jit", "broadcast_in_dim"}
    assert (4, model.num_params) in {shape for _, shape, _ in made}
    deltas, counts = step(w, xb, yb, frozen)
    assert np.isfinite(deltas).all() and np.asarray(deltas).any()
    assert np.asarray(counts["grouped_kernel"]).all()
    assert int(counts["dropped"].sum()) == 0


def test_two_peers_deltas_applied_once_are_both_applied(tiny):
    """The linearity the commitments and shares need: with `A` frozen and
    shared, the adapters under w + d1 + d2 ARE the base weights moved by
    the sum of the two peers' low-rank updates, (alpha / r) A (B1 + B2)."""
    model, frozen, w, x, y = tiny
    one = local_step_fn(model, "clipped_sgd", 1.0, 0.1)
    d1 = one(w, jnp.asarray(x[:2]), jnp.asarray(y[:2]), frozen)
    d2 = one(w, jnp.asarray(x[2:4]), jnp.asarray(y[2:4]), frozen)
    summed = model.apply_flat(w + (d1 + d2), jnp.asarray(x[4:5]), frozen)
    both = model.apply_flat((w + d1) + d2, jnp.asarray(x[4:5]), frozen)
    np.testing.assert_allclose(summed, both, atol=1e-6)
    # fold every peer's update into the base: same logits with B = 0
    scale = TINY.alpha / TINY.rank
    merged = jax.tree.map(lambda a: a, frozen)
    for layer, base, mine in zip(merged["layers"],
                                 model.unravel(w)["layers"],
                                 zip(model.unravel(d1)["layers"],
                                     model.unravel(d2)["layers"])):
        for name in "qkvo":
            moved = sum(scale * layer["lora_a"][name] @ part[name]
                        for part in (base,) + mine)
            layer["w" + name] = layer["w" + name] + moved
    folded = model.apply_flat(jnp.zeros_like(w), jnp.asarray(x[4:5]), merged)
    np.testing.assert_allclose(folded, summed, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_int32_float32_and_the_base_dtype_throughout_with_x64_on(dtype):
    assert jax.config.jax_enable_x64
    cfg = dataclasses.replace(TINY, dtype=dtype)
    model = laguna.laguna_model("laguna_tiny", cfg, 16)
    frozen = model.frozen(jax.random.PRNGKey(1))
    assert {str(a.dtype) for a in jax.tree.leaves(frozen)} == {dtype}
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}1")
    assert shard["x_train"].dtype == shard["y_train"].dtype == np.int32
    xb = jnp.asarray(shard["x_train"][:4]).reshape(2, 2, 16)
    yb = jnp.asarray(shard["y_train"][:4]).reshape(2, 2, 16)
    step = block_step_fn(model, "clipped_sgd", 1.0, 0.1)
    deltas, counts = jax.jit(step)(w, xb, yb, frozen)
    assert deltas.dtype == jnp.float32 and np.isfinite(deltas).all()
    assert {str(c.dtype) for c in jax.tree.leaves(counts)} == {"int32"}
    made = _all_shapes(jax.make_jaxpr(step)(w, xb, yb, frozen).jaxpr, [])
    wide = {(name, kind) for name, _, kind in made
            if kind in ("float64", "int64", "uint64")}
    assert not wide, wide
    if dtype == "bfloat16":  # and it is the float32 model, to bfloat16
        exact = laguna.laguna_model("laguna_tiny", TINY, 16)
        want, _ = jax.jit(block_step_fn(exact, "clipped_sgd", 1.0, 0.1))(
            w, xb, yb, jax.tree.map(lambda a: a.astype(jnp.float32), frozen))
        gap = jnp.linalg.norm(deltas - want) / jnp.linalg.norm(want)
        assert 1e-4 < float(gap) < 0.1


# ------------------------------------------------- the system's own path


def test_the_step_rule_is_declared_and_the_zoo_registers_the_model():
    assert set(laguna.PRESETS) <= set(MODELS)
    model = model_for_dataset(DATASET)
    assert model.name == "laguna_tiny" and model.step_rule == "clipped_sgd"
    assert model.token_input and model.d_in == 16 and model.n_classes == 64
    with pytest.raises(ValueError, match="token ids"):
        model_for_dataset("mnist", "laguna_tiny")
    with pytest.raises(ValueError, match="25088"):
        model_for_dataset(DATASET, "laguna_s_fedlora")


def test_the_round_trains_the_adapters_and_reports_its_routing():
    sim = the_round_trains_the_adapters_and_reports(FAMILY)
    # 4 peers x 2 windows x 16 tokens x 3 a token x 2 sparse layers, of
    # which about a quarter lands on the 4 of 16 experts held
    assert sim.dispatch_stats()["assignments_held"] < 768
    assert Simulator(cfg_of("", dataset="mnist",
                            num_nodes=4)).dispatch_stats() == {}


@pytest.mark.parametrize("name", [f"{DATASET}2", f"{DATASET}_bad2",
                                  f"{DATASET}_test", "lm_tokens5"])
def test_token_shards_are_the_same_by_name_twice(name):
    dataset = name.split("_test")[0].split("_bad")[0].rstrip("0123456789")
    first = {k: v.copy() for k, v in ds.load_shard(dataset, name).items()}
    ds.load_shard.cache_clear()
    again = ds.load_shard(dataset, name)
    spec = ds.spec(dataset)
    for key, value in first.items():
        np.testing.assert_array_equal(value, again[key])
        assert value.dtype == np.int32 and value.shape[1] == spec.d_in
        assert 0 <= value.min() and value.max() < spec.n_classes
    if "_test" in name:
        assert len(first["x_test"]) == 2
    else:
        assert len(first["x_train"]) % 8 == 0  # whole 8-row tiles
        np.testing.assert_array_equal(first["x_train"][:, 1:],
                                      first["y_train"][:, :-1]
                                      if "_bad" not in name
                                      else first["x_train"][:, 1:])
    if "_bad" in name:  # no class to flip: every label is the target
        assert (first["y_train"] == spec.attack_target).all()


def test_token_shards_differ_peer_by_peer():
    a = ds.load_shard("lm_tokens", "lm_tokens1")["x_train"]
    b = ds.load_shard("lm_tokens", "lm_tokens2")["x_train"]
    assert a.shape == b.shape == (64, 1024)
    top = lambda x: set(np.argsort(np.bincount(  # noqa: E731
        x.ravel(), minlength=25088))[-40:])
    assert 5 < len(top(a) & top(b)) < 35  # a shared law and a topic each
