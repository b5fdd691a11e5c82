"""Granite-4.0-H-Micro's Mamba-2 / attention hybrid with adapters
(models/granite_hybrid.py, ops/ssm.py, ops/attention.py) against the plain
float64 reference (benchmark/reference/granite_hybrid.py: the state-space
layer a token at a time), at the tiny preset: both kinds of layer in the
period's order, four chunks a 16-token window, a head group of 2, a tied
head, the four multipliers.

(Named `test_v3_...` so that it is collected LAST: the driver's workers
take files in alphabetical order, and a new heavy file in the middle moves
the neighbours of tests/test_runtime.py's live clusters; PR 31's lesson,
.claude/skills/verify/SKILL.md.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import granite_hybrid as ref
from biscotti_tpu.data import datasets as ds
from biscotti_tpu.models import granite_hybrid, lm
from biscotti_tpu.models.zoo import model_for_dataset
from biscotti_tpu.ops import ssm

DATASET = "lm_tokens_tiny"
NAME = "granite_h_tiny"
TINY = granite_hybrid.PRESETS[NAME]


def published(cfg):
    """The preset in the published config.json's keys: the reference's."""
    return {
        "hidden_size": cfg.hidden, "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads,
        "mamba_n_heads": cfg.ssm_heads, "mamba_d_head": cfg.ssm_head_dim,
        "mamba_d_state": cfg.ssm_state, "mamba_d_conv": cfg.conv,
        "mamba_chunk_size": cfg.chunk,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling, "rms_norm_eps": cfg.eps,
        "rope_theta": 10000, "lora_rank": cfg.rank, "lora_alpha": cfg.alpha}


@pytest.fixture(scope="module")
def tiny():
    model = model_for_dataset(DATASET, NAME)
    frozen = model.frozen(jax.random.PRNGKey(1))
    w = model.flat_init(jax.random.PRNGKey(2))
    shard = ds.load_shard(DATASET, f"{DATASET}0")
    return model, frozen, w, shard["x_train"], shard["y_train"]


def _ref64(variant=None):
    return ref.compiled(published(TINY), jnp.float64, variant)


# ---------------------------------------------------- the scan, ops/ssm.py


def _scan_inputs(windows=2, t=16, heads=3, p=4, n=5, dtype=jnp.float64):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    return (jax.random.normal(keys[0], (windows, t, heads, p), dtype),
            jax.nn.softplus(jax.random.normal(keys[1], (windows, t, heads),
                                              dtype)),
            -jnp.exp(jax.random.normal(keys[2], (heads,), dtype)),
            jax.random.normal(keys[3], (windows, t, n), dtype),
            jax.random.normal(keys[4], (windows, t, n), dtype),
            jax.random.normal(keys[5], (heads,), dtype))


def _token_by_token(x, dt, a, b, c, d):
    """The reference's recurrence (its own code), window by window."""
    y = jax.vmap(lambda *v: ref.recurrence(*v, 10**9, {}),
                 in_axes=(0, 0, None, 0, 0))(x, dt, a, b, c)
    return y + d[:, None] * x


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk):
    """Values and every gradient (x, dt, A, B, C, D), at chunks of 4, 8
    and the whole window: against the reference's recurrence and against
    `ssm.sequential`."""
    inputs = _scan_inputs()
    assert ssm.chunks(16, chunk) == 16 // chunk
    got = ssm.scan(*inputs, chunk)
    np.testing.assert_allclose(got, _token_by_token(*inputs), atol=1e-12)
    np.testing.assert_allclose(got, ssm.sequential(*inputs), atol=1e-12)

    def through(f):
        return jax.grad(lambda *v: jnp.sum(jnp.sin(f(*v))),
                        argnums=tuple(range(6)))(*inputs)

    want = through(_token_by_token)
    for name, g, r in zip("x dt a b c d".split(),
                          through(lambda *v: ssm.scan(*v, chunk)), want):
        assert np.isfinite(g).all() and np.abs(r).max() > 0, name
        np.testing.assert_allclose(g, r, atol=1e-11, err_msg=name)


def test_a_window_that_is_no_whole_number_of_chunks_is_refused():
    with pytest.raises(ValueError, match="whole number"):
        ssm.chunks(24, 16)
    assert ssm.chunks(16, 256) == 1  # shorter than a chunk: one chunk
    with pytest.raises(ValueError, match="whole number"):
        granite_hybrid.granite_hybrid_model("a", TINY, 18)


def test_the_state_starts_from_zero_at_every_window():
    """Two windows in one batch are the two alone: nothing is carried from
    a window to the next, whatever the chunk."""
    inputs = _scan_inputs()
    x, dt, a, b, c, d = inputs
    both = ssm.scan(*inputs, 4)
    for at in range(2):
        alone = ssm.scan(x[at:at + 1], dt[at:at + 1], a, b[at:at + 1],
                         c[at:at + 1], d, 4)
        np.testing.assert_array_equal(both[at:at + 1], alone)
    # and the second window's first token sees only itself
    np.testing.assert_allclose(
        both[1, 0], (dt[1, 0, :, None] * x[1, 0] * jnp.dot(b[1, 0], c[1, 0])
                     + d[:, None] * x[1, 0]), atol=1e-12)


def test_the_scans_operands_are_rounded_and_its_decays_are_not():
    """bfloat16 operands with float32 accumulation: close to the float32
    scan at bfloat16's resolution, far closer than a scan whose decays
    were held in bfloat16 would come."""
    x, dt, a, b, c, d = _scan_inputs(t=64, dtype=jnp.float32)
    exact = ssm.scan(x, dt, a, b, c, d, 16)
    low = ssm.scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                   c.astype(jnp.bfloat16), d, 16)
    assert low.dtype == jnp.float32
    gap = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < gap < 2e-2, gap


# ------------------------------------------------ the mixer's other parts


def test_the_conv_is_causal_depthwise_and_starts_from_nothing():
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(keys[0], (2, 9, 5), jnp.float32)
    weight = jax.random.normal(keys[1], (4, 5), jnp.float32)
    bias = jax.random.normal(keys[2], (5,), jnp.float32)
    got = granite_hybrid.causal_conv(x, weight, bias)
    want = np.zeros((2, 9, 5))
    for t in range(9):
        for k in range(4):
            if t + k - 3 >= 0:  # the tap weight[3] multiplies x_t itself
                want[:, t] += np.asarray(weight[k]) * np.asarray(
                    x[:, t + k - 3])
    np.testing.assert_allclose(got, want + np.asarray(bias), atol=1e-5)


def test_the_gate_comes_before_the_norm_and_its_gradient_follows():
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    y, z = (jax.random.normal(k, (3, 8), jnp.float64) for k in keys[:2])
    weight = 1.0 + 0.1 * jax.random.normal(keys[2], (8,), jnp.float64)

    def plain(y, z):
        u = y * z / (1.0 + jnp.exp(-z))
        return weight * u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True)
                                     + 1e-5)

    np.testing.assert_allclose(
        granite_hybrid.gated_norm(y, z, weight, 1e-5), plain(y, z),
        atol=1e-6)
    for at in (0, 1):  # with respect to y and to z
        got = jax.grad(lambda *v: jnp.sum(jnp.cos(
            granite_hybrid.gated_norm(*v, weight, 1e-5))), argnums=at)(y, z)
        want = jax.grad(lambda *v: jnp.sum(jnp.cos(plain(*v))),
                        argnums=at)(y, z)
        np.testing.assert_allclose(got, want, atol=1e-5)
    after = lm.rms(y, weight, 1e-5) * jax.nn.silu(z)  # the other order
    assert float(jnp.max(jnp.abs(after - plain(y, z)))) > 0.1


# ------------------------------------------------- against the reference


@pytest.mark.parametrize("windows", [1, 3])
def test_logits_match_the_reference(tiny, windows):
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:windows])
    got = model.apply_flat(w, tokens, frozen)
    want = _ref64()[1](frozen, w, tokens)
    assert got.shape == (windows, 16, 64) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)


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
    """Through the chunked scan's backward, the conv's, the gated norm's
    and the attention's, against `jax.grad` of the token-by-token
    reference."""
    model, frozen, w, x, y = tiny
    tokens, labels = jnp.asarray(x[:windows]), jnp.asarray(y[:windows])
    got = jax.grad(model.loss_flat)(w, tokens, labels, frozen)
    want = _ref64()[0](frozen, w, tokens, labels)
    spec = published(TINY)
    assert ref.num_params(spec) == model.num_params == got.shape[0]
    for (name, g), (_, r) in zip(ref.leaves(spec, np.asarray(got)),
                                 ref.leaves(spec, np.asarray(want))):
        assert np.linalg.norm(r) > 0, name  # every B counts in the loss
        np.testing.assert_allclose(g, r, atol=2e-7 + 1e-4 * np.abs(r).max(),
                                   err_msg=name)


def test_the_wire_vector_is_the_references_layout(tiny):
    model, _, w, _, _ = tiny
    tree = model.unravel(w)
    names = [name for name, _ in ref.layout(published(TINY))]
    assert names[:3] == ["layers[0].in", "layers[0].out", "layers[1].in"]
    assert names[4:8] == [f"layers[2].{n}" for n in "koqv"]
    for name, piece in ref.leaves(published(TINY), np.asarray(w)):
        layer, leaf = name.split(".")
        mine = tree["layers"][int(layer[len("layers["):-1])][leaf]
        np.testing.assert_array_equal(np.ravel(mine), piece, err_msg=name)


def test_the_head_is_the_embedding_one_leaf_counted_once(tiny):
    model, frozen, w, x, _ = tiny
    assert "head" not in frozen and frozen["embed"].shape == (64, 32)
    counted = sum(a.size for a in jax.tree.leaves(frozen))
    assert lm.frozen_count(model) == counted == 46452
    # the logits read the same leaf again: another embedding, other logits
    # through BOTH uses
    tokens = jnp.asarray(x[:1])
    h = granite_hybrid.hidden_states(TINY, lm.one_peer(model.unravel(w)),
                                     tokens[None], frozen, remat=False)[0]
    want = (lm.rms(h[0], frozen["final_norm"], TINY.eps)
            @ frozen["embed"].T) / TINY.logits_scaling
    np.testing.assert_allclose(model.apply_flat(w, tokens, frozen), want,
                               atol=1e-6)
    np.testing.assert_allclose(
        lm.embedded(TINY, tokens, frozen),
        TINY.embedding_multiplier * frozen["embed"][tokens], atol=0)


# (the reference's departure, the least it must move the logits by,
# relative): the four multipliers, the mixer's parts, the scan's carry
DEPARTURES = [
    ("embedding", {"embedding": 1.0}, 0.3),
    ("residual", {"residual": 1.0}, 0.3),
    ("logits_scaling", {"logits_scaling": 1.0}, 6.9),
    ("attention", {"attention": 0.125}, 1e-3),
    ("rotary", {"rotary": True}, 1e-3),
    ("no_d", {"d": False}, 0.05),
    ("no_conv_bias", {"conv_bias": False}, 0.03),
    ("no_dt_bias", {"dt_bias": False}, 0.02),
    ("gate_after_norm", {"gate_first": False}, 0.03),
    ("no_carry", {"carry": False}, 3e-3),
    ("decay_bfloat16", {"decay": "bfloat16"}, 2e-5),
]


@pytest.mark.parametrize("name,variant,least",
                         DEPARTURES, ids=[d[0] for d in DEPARTURES])
def test_every_departure_of_the_reference_moves_the_logits(tiny, name,
                                                           variant, least):
    """The program sits on the reference (1e-6, relative) and every
    control's departure far from both: each multiplier, the conv's bias,
    D, dt_bias, the gate's place and the carried state are in the
    program."""
    model, frozen, w, x, _ = tiny
    tokens = jnp.asarray(x[:2])
    want = np.asarray(_ref64()[1](frozen, w, tokens))
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    other = np.asarray(_ref64(variant)[1](frozen, w, tokens))
    scale = np.linalg.norm(want)
    assert np.linalg.norm(got - want) / scale < 1e-6
    assert np.linalg.norm(other - want) / scale > least, name


def test_the_published_dtype_runs_close_to_the_reference():
    """bfloat16 base and operands, float32 accumulation (the published
    size's arithmetic, here at the tiny widths): within bfloat16's
    resolution of the float64 reference on the same rounded weights."""
    import dataclasses

    cfg = dataclasses.replace(TINY, dtype="bfloat16")
    model = granite_hybrid.granite_hybrid_model("granite_h_tiny_bf16", cfg,
                                                16)
    frozen = model.frozen(jax.random.PRNGKey(1))
    assert frozen["layers"][0]["a_log"].dtype == jnp.bfloat16
    w = model.flat_init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(ds.load_shard(DATASET, f"{DATASET}0")["x_train"][:2])
    got = np.asarray(model.apply_flat(w, tokens, frozen), np.float64)
    want = np.asarray(_ref64()[1](frozen, w, tokens))
    gap = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert 1e-4 < gap < 3e-2, gap


def test_the_frozen_scalars_follow_mamba2s_own_laws():
    model = model_for_dataset(DATASET, NAME)
    layer = model.frozen(jax.random.PRNGKey(7))["layers"][0]
    a = np.exp(np.asarray(layer["a_log"], np.float64))
    assert ((a >= 1.0) & (a <= 16.0)).all()
    step = np.log1p(np.exp(np.asarray(layer["dt_bias"], np.float64)))
    assert ((step >= 0.99e-3) & (step <= 0.101)).all()
    assert abs(float(np.mean(layer["d"])) - 1.0) < 0.3
    assert layer["conv_w"].shape == (4, 4 * 16 + 2 * 8)
