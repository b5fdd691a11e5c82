"""DeepSeek-V2's decoder share with adapters (models/deepseek_v2.py,
ops/moe.py, ops/attention.py) against the plain float64 reference
(benchmark/reference/deepseek_v2.py), at the tiny preset: a score width (6)
unlike the value width (4), one rotary key for all heads, inner norms on
both latents, 2 of 4 groups kept, three experts a token with unnormalised
coefficients, 4 of 16 experts held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v2 as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import deepseek_v2
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import attention as at
from biscotti_tpu.ops import moe, rotary

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


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:windows])
    got = model.apply_flat(w, tokens, frozen)
    want, _ = _ref64(TINY)[1](frozen, w, tokens)
    assert got.shape == (windows, 16, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("windows", [1, 3])
def test_loss_matches_the_reference(tiny, windows):
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    want = jax.jit(lambda frozen, w, tokens, labels: ref.loss(
        published(TINY), frozen, ref.unflatten(published(TINY), w,
                                               jnp.float64),
        tokens, labels, jnp.float64))(frozen, w, tokens, labels)
    np.testing.assert_allclose(model.loss_flat(w, tokens, labels, frozen),
                               want, rtol=1e-5)


@pytest.mark.parametrize("windows", [1, 2])
def test_every_adapter_gradient_matches_the_reference(tiny, windows):
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    got = jax.grad(model.loss_flat)(w, tokens, labels, frozen)
    want = _ref64(TINY)[0](frozen, w, tokens, labels)
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
    for name, piece in ref.leaves(published(TINY), np.asarray(w)):
        layer, leaf = name.split(".")
        mine = tree["layers"][int(layer[len("layers["):-1])][leaf]
        np.testing.assert_array_equal(np.ravel(mine), piece, err_msg=name)


def test_the_router_picks_what_the_reference_picks(tiny):
    model, frozen, w, x, _ = tiny
    chosen, probs = deepseek_v2.routing(TINY, model.unravel(w),
                                        jnp.asarray(x[:2]), frozen)
    _, picks = _ref64(TINY)[1](frozen, w, jnp.asarray(x[:2]))
    assert chosen.shape == (2, 32, 3) and chosen.dtype == jnp.int32
    for got, got_p, (want, want_p) in zip(chosen, probs, picks):
        np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))
        np.testing.assert_allclose(got_p, want_p, atol=1e-6)
        # at most `groups_kept` of the four groups of 4
        assert max(len(set(row // 4)) for row in np.asarray(got)) <= 2


# ------------------------------------------------------------ the router


def _reference_route(probs, groups, kept, top_k):
    """Group-limited greedy in plain numpy: (chosen sets, the probability
    of the last chosen, of the first left out among the eligible)."""
    chosen = []
    for p in probs:
        size = len(p) // groups
        best = p.reshape(groups, size).max(axis=1)
        keep = np.argsort(-best, kind="stable")[:kept]
        eligible = np.where(np.isin(np.arange(len(p)) // size, keep), p, 0.0)
        chosen.append(set(np.argsort(-eligible, kind="stable")[:top_k]))
    return chosen


@pytest.mark.parametrize("groups,kept", [(1, 1), (4, 2), (8, 3), (8, 8)])
def test_route_with_groups_is_the_plain_rule_near_ties_included(groups,
                                                                kept):
    key = jax.random.PRNGKey(11)
    n, hidden, experts, top_k = 96, 16, 32, 5
    x = jax.random.normal(key, (n, hidden), jnp.float32)
    router = jax.random.normal(jax.random.fold_in(key, 1), (hidden, experts))
    # near-ties: pairs of router columns a rounding apart, and two equal
    router = router.at[:, 7].set(router[:, 3] * (1 + 1e-7))
    router = router.at[:, 21].set(router[:, 20]).astype(jnp.float32)
    got, coef, probs = moe.route(x, router, top_k, 16.0, groups, kept, False)
    assert got.dtype == jnp.int32 and got.shape == (n, top_k)
    np.testing.assert_allclose(jnp.sum(probs, -1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(
        coef, 16.0 * np.take_along_axis(np.asarray(probs), got, 1),
        rtol=1e-6)  # NOT renormalised
    want = _reference_route(np.asarray(probs), groups, kept, top_k)
    for row, (mine, theirs) in enumerate(zip(np.asarray(got), want)):
        if set(mine) != theirs:  # only where two probabilities tie
            p = np.asarray(probs)[row]
            odd = sorted(set(mine) ^ theirs)
            assert np.ptp(p[odd]) <= 1e-7 * p[odd].max(), (row, odd)
    if groups == 8 and kept == 8 or groups == 1:
        plain, _, _ = moe.route(x, router, top_k, 16.0)
        np.testing.assert_array_equal(got, plain)


def test_lagunas_call_of_route_is_bit_for_bit_what_it_was():
    """One group, renormalised: the defaults are the parent's `route`,
    instruction for instruction (its lowered text) and value for value."""
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16), jnp.float32)
    router = jax.random.normal(jax.random.PRNGKey(1), (16, 12), jnp.float32)

    def parent(x, router_w, top_k, scale):  # moe.route as of PR 30
        logits = jnp.dot(x.astype(router_w.dtype), router_w,
                         preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, top_k)
        coef = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        return top_i.astype(jnp.int32), coef, probs

    def mine(x, router_w, top_k, scale):
        return moe.route(x, router_w, top_k, scale)

    texts = [jax.jit(f, static_argnums=(2, 3)).lower(
        x, router, 3, 2.5).as_text().replace(f.__name__, "route")
        for f in (parent, mine)]
    assert texts[0] == texts[1]
    for a, b in zip(parent(x, router, 3, 2.5), mine(x, router, 3, 2.5)):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------- the attention core


def _mla_inputs(dtype, t=256, heads=3, windows=2, d=192, e=128):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (windows, heads, 1, t, d), jnp.float32)
    k = jax.random.normal(keys[1], (windows, heads, t, d), jnp.float32)
    v = jax.random.normal(keys[2], (windows, heads, t, e), jnp.float32)
    cot = jax.random.normal(keys[3], (windows, heads, 1, t, e), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), cot


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("block", [(128, 128), (256, 128), (128, 256)])
def test_the_kernel_at_a_score_width_unlike_the_value_width(dtype, tol,
                                                            block):
    """Scores that contract 192, values of 128, no head shared, a scale of
    its own: the kernel (interpreted) is the `einsum` form, forward and
    backward."""
    q, k, v, cot = _mla_inputs(jnp.dtype(dtype))
    scale = 192 ** -0.5 * 1.2608 ** 2

    def both(form):
        out, back = jax.vjp(form, q, k, v)
        return (out,) + back(cot)

    want = both(lambda *a: at.plain(*a, 256, scale))
    got = both(lambda *a: at.fused(*a, 256, block, scale))
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.max(np.abs(a - b)) <= tol * np.max(np.abs(b)), name
    assert got[0].shape == (2, 3, 1, 256, 128)
    assert got[0].dtype == jnp.float32


def _assembled(k, shared):
    """k = [k_nope | the one shared part, broadcast to every head]."""
    return jnp.concatenate(
        [k, jnp.broadcast_to(shared, k.shape[:-1] + shared.shape[-1:])], -1)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("block", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("kv,g", [(3, 1), (2, 2)])
def test_a_shared_key_part_is_the_assembled_key(dtype, tol, block, kv, g):
    """The core given `k_nope` [W, kv, T, 128] and ONE key part [W, 1, T,
    64] for all heads: the kernel (interpreted), the `einsum` form with the
    same operand, and the `einsum` form on the assembled 192-wide key are
    one function, forward and backward; the part's cotangent is the sum
    over the heads of the assembled key's last 64. Two windows, so a sum
    that is not zeroed a window shows."""
    dtype, t, own, r = jnp.dtype(dtype), 256, 128, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(keys[0], (2, kv, g, t, own + r)).astype(dtype)
    k = jax.random.normal(keys[1], (2, kv, t, own)).astype(dtype)
    shared = jax.random.normal(keys[2], (2, 1, t, r)).astype(dtype)
    v = jax.random.normal(keys[3], (2, kv, t, 128)).astype(dtype)
    cot = jax.random.normal(keys[4], (2, kv, g, t, 128), jnp.float32)
    scale = 192 ** -0.5 * 1.2608 ** 2

    def both(form, *operands):
        out, back = jax.vjp(form, *operands)
        return (out,) + back(cot)

    out, dq, dk_whole, dv = both(lambda *a: at.plain(*a, t, scale), q,
                                 _assembled(k, shared), v)
    want = (out, dq, dk_whole[..., :own], dv, jnp.sum(
        dk_whole[..., own:].astype(jnp.float32), 1, keepdims=True))
    names = ("out", "dq", "dk_nope", "dv", "dshared")
    for form in (lambda q, k, v, s: at.fused(q, k, v, t, block, scale, s),
                 lambda q, k, v, s: at.plain(q, k, v, t, scale, s)):
        got = both(form, q, k, v, shared)
        for name, a, b in zip(names, got, want):
            assert a.shape == b.shape, name
            assert a.dtype == (jnp.float32 if name == "out" else dtype), name
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            assert np.max(np.abs(a - b)) <= tol * np.max(np.abs(b)), name
    # and `attention` reads the operand list off what it was given
    np.testing.assert_array_equal(at.attention(q, k, v, t, scale, shared),
                                  at.fused(q, k, v, t, None, scale, shared))


def test_blocks_decides_from_the_shapes_alone():
    bf16 = jnp.bfloat16
    # the published MLA core and Laguna's two, unchanged
    assert at.blocks(1, 1024, 192, bf16, 128) == (256, 512)
    # and with the 64 rotary dimensions as a key part all heads share: k is
    # 128 wide, the part is held like k, the count is the same
    assert at.blocks(1, 1024, 192, bf16, 128, 64) == (256, 512)
    assert (at._buffers(1, 1024, 192, 256, 512, 2, 128, 64)
            == at._buffers(1, 1024, 192, 256, 512, 2, 128) <= at._VMEM_BUFFERS)
    assert at.blocks(1, 1024, 192, bf16, 128, 32) is None
    assert at.blocks(6, 1024, 128, bf16) == at.blocks(6, 1024, 128, bf16,
                                                      128) == (256, 512)
    assert at.blocks(9, 1024, 128, bf16) == (256, 512)
    # the tiny presets, a value or a score width not of 64 (since PR 33 a
    # value width of half a lane tile is taken as it is: Granite's 64 | 64)
    assert at.blocks(1, 16, 6, jnp.float32, 4) is None
    assert at.blocks(1, 1024, 192, bf16, 96) is None
    assert at.blocks(1, 1024, 160, bf16, 128) is None
    assert at.blocks(4, 1024, 64, bf16, 64) == (256, 512)
    # the einsum side takes the scale and the two widths too
    q, k, v, _ = _mla_inputs(jnp.float32, t=16, d=6, e=4)
    out = at.attention(q, k, v, 16, 0.3)
    np.testing.assert_allclose(out, at.plain(q, k, v, 16, 0.3))
    assert out.shape == (2, 3, 1, 16, 4)


# ------------------------------------------------------ the expert layer


def test_the_four_shares_add_up_through_the_whole_layer(tiny):
    """The model's own sparse layer on each of four chips' 4 of the 16
    experts, against the reference's UNCUT layer: four shares' results
    less three times what every chip computes alike (the residual, the
    attention, the two shared experts), so the shared experts count
    once."""
    model, frozen, w, x, _ = tiny
    spec, layer = published(TINY), 1
    key = jax.random.PRNGKey(5)
    full = dict(frozen["layers"][layer])
    full["experts"] = {
        name: jax.random.normal(jax.random.fold_in(key, i),
                                (16,) + leaf.shape[1:], jnp.float32) / 5
        for i, (name, leaf) in enumerate(sorted(full["experts"].items()))}
    h = frozen["embed"][jnp.asarray(x[:2])][None]         # [1, 2, T, H]
    adapters = jax.tree.map(lambda a: a[None], model.unravel(w))
    lora64 = ref.unflatten(spec, w, jnp.float64)[layer]
    uncut, _ = ref.layer(spec, layer, jnp.asarray(h[0], jnp.float64), full,
                         lora64, jnp.float64, {})
    none = dict(full, experts=jax.tree.map(lambda a: a[:0], full["experts"]))
    alike, _ = ref.layer(spec, layer, jnp.asarray(h[0], jnp.float64), none,
                         lora64, jnp.float64, {})
    total, held = 0.0, 0
    for share in range(4):
        cfg = dataclasses.replace(TINY, first_expert=4 * share)
        mine = dict(full, experts=jax.tree.map(
            lambda a: a[4 * share:4 * share + 4], full["experts"]))
        out, counts, _ = deepseek_v2._layer(cfg, layer, h, mine,
                                            adapters["layers"][layer])
        total = total + np.asarray(out[0], np.float64)
        held += int(counts["load"].sum())
        assert int(counts["dropped"]) == 0
    assert held == 2 * 16 * TINY.top_k  # every assignment, once
    np.testing.assert_allclose(total - 3 * np.asarray(alike), uncut,
                               atol=2e-4)
    # and without the shared experts the reference's layer is another
    bare, _ = ref.layer(spec, layer, jnp.asarray(h[0], jnp.float64), full,
                        lora64, jnp.float64, {"shared": False})
    assert float(jnp.max(jnp.abs(bare - uncut))) > 1e-2


@pytest.mark.parametrize("variant", [
    {"fewer_experts": 1}, {"groups": False}, {"renormalise": True},
    {"scale": 1.0}, {"shared_rope": False}, {"inner_norms": False},
    {"mscale": False}, {"shared": False}])
def test_every_departure_of_the_reference_moves_the_logits(tiny, variant):
    """Each control of the benchmark's cell is a different model: its
    logits leave the sound reference's by far more than rounding."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    sound, _ = _ref64(TINY)[1](frozen, w, tokens)
    other, _ = ref.compiled(published(TINY), jnp.float64, variant)[1](
        frozen, w, tokens)
    gap = float(jnp.linalg.norm(other - sound) / jnp.linalg.norm(sound))
    assert gap > 1e-3, (variant, gap)


def test_rotary_is_interleaved_pairs_under_yarn():
    """Dimensions (2i, 2i + 1) turn by position x frequency i; the tables
    are the reference's (DeepSeek's own construction), cos and sin times
    mscale / mscale_all_dim = 1; the softmax scale carries m^2."""
    cos, sin = deepseek_v2.rotary_tables(TINY, 16)
    want_cos, want_sin = ref.rotary(published(TINY), 16)
    np.testing.assert_allclose(cos, want_cos[:, :1], atol=1e-6)
    np.testing.assert_allclose(sin, want_sin[:, :1], atol=1e-6)
    big = deepseek_v2.PRESETS["deepseek_v2_fedlora"]
    cos, sin = deepseek_v2.rotary_tables(big, 1024)
    want_cos, want_sin = ref.rotary(published(big), 1024)
    np.testing.assert_allclose(cos, want_cos[:, :32], atol=2e-6)
    np.testing.assert_allclose(sin, want_sin[:, :32], atol=2e-6)
    np.testing.assert_allclose(np.hypot(cos, sin), 1.0, atol=1e-6)
    np.testing.assert_allclose(deepseek_v2.softmax_scale(big),
                               192 ** -0.5 * 1.2608 ** 2, rtol=1e-4)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 2), jnp.float32)
    cos, sin = deepseek_v2.rotary_tables(TINY, 16)
    turned = rotary.turn(x[None], *rotary.tables(cos, sin, 2),
                         jnp.float32)[0, 0]
    angle = np.arctan2(np.asarray(sin[:, 0]), np.asarray(cos[:, 0]))
    want = np.stack([x[:, 0] * np.cos(angle) - x[:, 1] * np.sin(angle),
                     x[:, 1] * np.cos(angle) + x[:, 0] * np.sin(angle)], 1)
    np.testing.assert_allclose(turned, want, atol=1e-6)


def _halves(x, cos, sin):
    """Rotary in interleaved pairs as DeepSeek's own code leaves it (and
    this model did until PR 37): the pairs' first halves, then their
    second."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@pytest.mark.parametrize("side", ["compiler", "kernel"])
def test_q_and_the_key_turn_in_the_same_order(side):
    """In place (ops/rotary.py) q's and the key's turned dimensions stay
    where they were, every head's first 128 untouched: each score is the
    sum of the same products as under `_halves`, on the kernel's side
    (heads that come to whole lane tiles, interpreted) and the compiler's."""
    big = deepseek_v2.PRESETS["deepseek_v2_fedlora"]
    t, n = 64, 2 if side == "kernel" else 3
    cos, sin = deepseek_v2.rotary_tables(big, t)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    q = jax.random.normal(keys[0], (1, t, n * 192), jnp.float32)
    k_r = jax.random.normal(keys[1], (1, t, 64), jnp.float32)
    assert (rotary.rows(t, n * 192, 192, 4, 4) is not None) == (
        side == "kernel")
    assert rotary.rows(t, 64, 64, 4, 4) is None  # the one key: the compiler's
    got_q = np.asarray(rotary.turn(q, *rotary.tables(cos, sin, 192),
                                   jnp.float32), np.float64)[0]
    got_k = np.asarray(rotary.turn(k_r, *rotary.tables(cos, sin, 64),
                                   jnp.float32), np.float64)[0, 0]
    assert got_q.shape == (n, t, 192) and got_k.shape == (t, 64)
    heads = np.asarray(q).reshape(t, n, 192)
    np.testing.assert_array_equal(got_q[..., :128],
                                  heads[..., :128].transpose(1, 0, 2))
    want_k = np.asarray(_halves(k_r[0], cos, sin), np.float64)
    for h in range(n):
        want_q = np.asarray(_halves(heads[:, h, 128:], cos, sin), np.float64)
        scores = got_q[h, :, 128:] @ got_k.T
        np.testing.assert_allclose(scores, want_q @ want_k.T,
                                   atol=1e-6 * np.abs(scores).max())


@pytest.mark.parametrize("operand,result", [("float32", "bfloat16"),
                                            ("bfloat16", "float32")])
def test_the_turn_is_one_pass_and_its_transpose_is_the_turn_back(operand,
                                                                 result):
    """The kernels (interpreted) are the compiler's form, the forward's
    float32 token-major rows into the base's type head-major and the
    backward's cotangent back; the gradient is the cotangent turned by the
    negated sine."""
    t, n, d = 64, 4, 192
    angles = np.random.RandomState(0).uniform(0, 6, (t, 32))
    cos, sin = rotary.tables(np.cos(angles), np.sin(angles), d)
    assert cos.shape == sin.shape == (t, d) and (cos[:, :128] == 1).all()
    assert not sin[:, :128].any()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, t, n * d)).astype(
        operand)
    assert rotary.rows(t, n * d, d, 4, 2) == 64
    assert rotary.rows(1024, 128 * 192, 192, 4, 2) == 32  # the published q
    got = rotary.turn(x, cos, sin, result)
    want = rotary.plain(x, cos, sin, result)
    assert got.dtype == want.dtype == jnp.dtype(result)
    assert got.shape == want.shape == (2, n, t, d)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-6)
    cot = jax.random.normal(jax.random.PRNGKey(1), got.shape).astype(result)
    _, back = jax.vjp(lambda x: rotary.turn(x, cos, sin, result), x)
    _, plain_back = jax.vjp(lambda x: rotary.plain(x, cos, sin, result), x)
    (dx,), (plain_dx,) = back(cot), plain_back(cot)
    assert dx.dtype == x.dtype and dx.shape == x.shape
    for other in (plain_dx, rotary._plain_back(cot, cos, -sin, operand)):
        np.testing.assert_allclose(np.asarray(dx, np.float32),
                                   np.asarray(other, np.float32),
                                   atol=2e-2 if operand == "bfloat16"
                                   else 1e-6)
