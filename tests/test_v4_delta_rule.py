"""The gated delta rule (ops/delta_rule.py) alone, no model built: the
`jax.numpy` chunked form against the token-by-token recurrence (the plain
reference's own, benchmark/reference/qwen3_next.py) in float64, and the
fused kernel (interpret mode here) against both: values and all five
gradients, the rounding contract, the solve where a chunk is hard, windows
that never meet, a window shorter than a chunk, and the dispatch between
the two forms. The model that runs the rule is tests/test_v4_qwen3_next.py's.

(A file of its own since PR 46: these are 350 of the 545 CPU-seconds the
model's file took on one worker, and `--dist loadfile` spreads files, not
tests.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as ref
from biscotti_tpu.ops import delta_rule


# --------------------------------------------- the rule, ops/delta_rule.py


def _rule_inputs(windows=2, t=16, groups=2, each=2, d=4, e=5,
                 dtype=jnp.float64):
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    heads = groups * each
    return (delta_rule.l2norm(jax.random.normal(
                keys[0], (windows, t, groups, d), dtype)) * d ** -0.5,
            delta_rule.l2norm(jax.random.normal(
                keys[1], (windows, t, groups, d), dtype)),
            jax.random.normal(keys[2], (windows, t, heads, e), dtype),
            -0.3 * jax.nn.softplus(jax.random.normal(
                keys[3], (windows, t, heads), dtype)),
            jax.nn.sigmoid(jax.random.normal(keys[4], (windows, t, heads),
                                             dtype)))


def _token_by_token(q, k, v, g, beta):
    """The reference's recurrence (its own code), window by window."""
    return jax.vmap(lambda *a: ref.delta_rule(*a, {}))(q, k, v, g, beta)


@pytest.mark.parametrize("t,chunk", [(16, 4), (16, 8), (16, 16), (12, 64),
                                     (64, 64), (96, 32)])
def test_the_chunked_rule_is_the_token_by_token_recurrence(t, chunk):
    """Values and every gradient (q, k, v, g, beta) in float64, at windows
    of four, two and one chunk, at one SHORTER than a chunk (three blocks
    of four rows in its solve) and at chunks of 64 and 32 (four and two
    blocks of `SUB` rows): against the reference's recurrence and against
    `delta_rule.sequential`. 1e-12: the two forms differ by the order of
    float64 sums alone."""
    inputs = _rule_inputs(t=t)
    assert delta_rule.chunks(t, chunk) == max(1, t // chunk)
    assert delta_rule.SUB == 16
    got = delta_rule.chunked(*inputs, chunk)
    assert got.shape == (2, t, 4, 5)
    np.testing.assert_allclose(got, _token_by_token(*inputs), atol=1e-12)
    np.testing.assert_allclose(got, delta_rule.sequential(*inputs),
                               atol=1e-12)

    def through(f):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                        argnums=tuple(range(5)))(*inputs)

    want = through(_token_by_token)
    for name, g, r in zip("q k v g beta".split(),
                          through(lambda *a: delta_rule.chunked(*a, chunk)),
                          want):
        assert np.isfinite(g).all() and np.abs(r).max() > 0, name
        np.testing.assert_allclose(g, r, atol=1e-11, err_msg=name)


def test_the_rules_state_starts_from_zero_at_every_window():
    """Two windows in one batch are the two alone, whatever the chunk, and
    a window's first token sees only itself: o_0 = beta_0 (k_0 . q_0)
    v_0."""
    q, k, v, g, beta = inputs = _rule_inputs()
    both = delta_rule.chunked(*inputs, 4)
    for at in range(2):
        alone = delta_rule.chunked(*(a[at:at + 1] for a in inputs), 4)
        np.testing.assert_allclose(both[at:at + 1], alone, atol=1e-15)
    kq = jnp.repeat(jnp.sum(k[1, 0] * q[1, 0], -1), 2)       # [H]
    np.testing.assert_allclose(both[1, 0],
                               (beta[1, 0] * kq)[:, None] * v[1, 0],
                               atol=1e-12)


def test_a_key_head_serves_its_value_heads():
    """Value head h reads key head h // (H / G): two value heads a key
    head are four heads on q and k written out twice."""
    q, k, v, g, beta = _rule_inputs()
    apart = delta_rule.chunked(jnp.repeat(q, 2, axis=2),
                               jnp.repeat(k, 2, axis=2), v, g, beta, 4)
    np.testing.assert_allclose(delta_rule.chunked(q, k, v, g, beta, 4),
                               apart, atol=1e-14)


def test_the_correction_is_in_the_rule():
    """Without `S^T k` in d the rule is plain gated linear attention (the
    reference's `no_delta`), with beta = 1 another: both far from it."""
    inputs = _rule_inputs()
    got = delta_rule.chunked(*inputs, 4)
    for variant in ({"delta": False}, {"beta": 1.0}):
        q, k, v, g, beta = inputs
        if "beta" in variant:
            beta = jnp.ones_like(beta)
        other = jax.vmap(lambda *a: ref.delta_rule(*a, variant))(
            q, k, v, g, beta)
        assert float(jnp.max(jnp.abs(other - got))) > 0.05, variant


def test_the_rules_operands_are_rounded_and_its_decays_are_not():
    """bfloat16 operands with float32 accumulation, the solve and the
    carried state in float32: close to the float32 rule at bfloat16's
    resolution."""
    q, k, v, g, beta = _rule_inputs(t=64, d=16, e=16, dtype=jnp.float32)
    exact = delta_rule.chunked(q, k, v, g, beta, 16)
    low = delta_rule.chunked(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                             g, beta, 16)
    assert low.dtype == jnp.float32
    gap = float(jnp.linalg.norm(low - exact) / jnp.linalg.norm(exact))
    assert 1e-4 < gap < 2e-2, gap


def test_l2norm_divides_by_the_length():
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 7), jnp.float64)
    got = delta_rule.l2norm(x, 1e-6)
    np.testing.assert_allclose(
        got, x / np.sqrt(np.sum(np.square(x), -1, keepdims=True) + 1e-6),
        atol=1e-14)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1), 1.0, atol=1e-6)


# ------------------------------ the rule's kernel, ops/delta_rule.py (PR 39)
# (at the END of the file: the tests above run on the schedule they had, and
# these, the heaviest, after the live clusters of other files are done)


def _wide_inputs(windows=1, t=128, groups=1, dtype=jnp.float32, g=None,
                 keys=None, d=128, e=128, each=2, top=1.0):
    """Operands as the model hands them over, at lane-tile widths (D = E =
    128, two value heads a key head) unless `d`, `e`, `each` say
    otherwise: q, k normalised, g <= 0 a softplus (or the constant `g`),
    beta = `top` sigmoid(.), `keys` "one": every key of a window the same
    unit vector."""
    ks = jax.random.split(jax.random.PRNGKey(39), 6)
    heads = each * groups
    q = delta_rule.l2norm(jax.random.normal(
        ks[0], (windows, t, groups, d), jnp.float32)) * d ** -0.5
    k = delta_rule.l2norm(jax.random.normal(
        ks[1], (windows, 1 if keys == "one" else t, groups, d),
        jnp.float32))
    k = jnp.broadcast_to(k, q.shape)
    v = jax.random.normal(ks[2], (windows, t, heads, e), jnp.float32)
    decay = -jax.nn.softplus(jax.random.normal(
        ks[3], (windows, t, heads), jnp.float32) - 3.0)
    if g is not None:
        decay = jnp.full_like(decay, g)
    beta = top * jax.nn.sigmoid(jax.random.normal(
        ks[4], (windows, t, heads), jnp.float32))
    cot = jax.random.normal(ks[5], v.shape, jnp.float32)
    return tuple(a.astype(dtype) for a in (q, k, v)) + (decay, beta), cot


def _value_and_gradients(form, inputs, cot):
    out, back = jax.vjp(form, *inputs)
    return (out,) + back(cot)


def _sequential32(q, k, v, g, beta):
    return delta_rule.sequential(*(a.astype(jnp.float32) for a in (q, k, v)),
                                 g, beta)


def _gaps(got, want):
    return [float(jnp.linalg.norm((a - r).astype(jnp.float32))
                  / jnp.linalg.norm(r.astype(jnp.float32)))
            for a, r in zip(got, want)]


NAMES = "o dq dk dv dg dbeta".split()


@pytest.mark.parametrize("windows,t,groups", [(1, 128, 1), (2, 128, 2),
                                              (1, 256, 2), (2, 256, 1)])
def test_the_kernel_is_the_rule_in_float32(windows, t, groups):
    """The fused kernel (interpret mode here) against the `jax.numpy`
    chunked form and the token-by-token recurrence at D = E = 128 and
    chunks of 64: o and all five gradients, float32 operands, 1e-5 of the
    largest entry."""
    inputs, cot = _wide_inputs(windows, t, groups)
    assert delta_rule.fits(t, 128, 128, 64, jnp.float32)
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    assert got[0].shape == (windows, t, 2 * groups, 128)
    assert got[0].dtype == jnp.float32
    for form in (lambda *a: delta_rule.chunked(*a, 64), _sequential32):
        want = _value_and_gradients(form, inputs, cot)
        for name, a, r in zip(NAMES, got, want):
            assert a.shape == r.shape and a.dtype == r.dtype, name
            scale = float(jnp.max(jnp.abs(r)))
            assert scale > 0, name
            np.testing.assert_allclose(a, r, atol=1e-5 * max(scale, 1.0),
                                       err_msg=name)


@pytest.mark.parametrize("windows,t,groups", [(1, 128, 2), (2, 256, 1)])
def test_the_kernel_rounds_its_operands_as_the_chunked_form_does(
        windows, t, groups):
    """bfloat16 operands: the kernel as far from the float32 recurrence as
    the `jax.numpy` form is on the chip (eval/eval_delta_rule.py's gaps,
    PERF.md section 6: 0.0025 on o, 0.0037 on dq and dk; the cotangents
    are rounded to the operands' type before their products, as the
    chip's default precision rounds them)."""
    inputs, cot = _wide_inputs(windows, t, groups, jnp.bfloat16)
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    assert [a.dtype for a in got] == [jnp.float32] + 3 * [jnp.bfloat16] \
        + 2 * [jnp.float32]
    want = _value_and_gradients(_sequential32, inputs, cot)
    gaps = dict(zip(NAMES, _gaps(got, want)))
    assert gaps["o"] < 0.003, gaps
    assert max(gaps.values()) < 0.0045, gaps
    same = _value_and_gradients(lambda *a: delta_rule.chunked(*a, 64),
                                inputs, cot)
    assert _gaps(got[:1], same[:1])[0] < 1e-4  # o: the same rounded sums


@pytest.mark.parametrize("case", ["one_key", "strong_decay", "no_decay"])
def test_the_kernels_solve_holds_where_a_chunk_is_hard(case):
    """A chunk whose keys are ALL one vector (A = beta decay everywhere
    below the diagonal: the case the product form of (I + A)^-1 loses, its
    powers of A grow to 2^63), g near -20 (every decay underflows: a masked
    decay must be exp(-inf), and exp(gamma_L - gamma) up to e^1280 must
    never be formed) and g = 0 (no decay at all): values and gradients
    finite and the recurrence's."""
    inputs, cot = _wide_inputs(
        1, 128, 1, keys="one" if case == "one_key" else None,
        g={"one_key": None, "strong_decay": -20.0, "no_decay": 0.0}[case])
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    want = _value_and_gradients(_sequential32, inputs, cot)
    for name, a, r in zip(NAMES, got, want):
        assert np.isfinite(a).all(), name
        scale = max(float(jnp.max(jnp.abs(r))), 1.0)
        np.testing.assert_allclose(a, r, atol=2e-5 * scale, err_msg=name)


def test_the_kernels_windows_never_meet():
    """A batch of two windows is two batches of one, bit for bit, values
    and gradients: the state starts from zero at a window's first chunk
    and the cotangent of the state at its last."""
    inputs, cot = _wide_inputs(2, 128, 1, jnp.bfloat16)
    both = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                                cot)
    for at in range(2):
        alone = _value_and_gradients(
            lambda *a: delta_rule.rule(*a, 64),
            tuple(a[at:at + 1] for a in inputs), cot[at:at + 1])
        for name, a, r in zip(NAMES, both, alone):
            np.testing.assert_array_equal(a[at:at + 1], r, err_msg=name)


def test_the_kernel_takes_a_window_shorter_than_a_chunk():
    """32 tokens in chunks of 64: one chunk of 32 (two blocks of `SUB`
    rows in its solve)."""
    inputs, cot = _wide_inputs(1, 32, 1)
    assert delta_rule.fits(32, 128, 128, 64, jnp.float32)
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    want = _value_and_gradients(_sequential32, inputs, cot)
    for name, a, r in zip(NAMES, got, want):
        np.testing.assert_allclose(
            a, r, atol=1e-5 * max(float(jnp.max(jnp.abs(r))), 1.0),
            err_msg=name)


@pytest.mark.parametrize("case", ["float64", "narrow", "ragged_chunk"])
def test_the_rule_keeps_to_jax_numpy_where_the_kernel_does_not_fit(
        case, monkeypatch):
    """float64 operands (the tests' own), a key width of half a lane tile,
    a window of 24 tokens (no whole block of `SUB` rows): `rule` is
    `chunked` there, bit for bit, and the kernel is never traced."""
    def refuse(*a):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(delta_rule, "fused", refuse)
    if case == "narrow":
        inputs = _rule_inputs(1, 64, 1, 2, 64, 128, jnp.float32)
    else:
        inputs, _ = _wide_inputs(1, 24 if case == "ragged_chunk" else 64, 1)
    if case == "float64":
        inputs = tuple(a.astype(jnp.float64) for a in inputs)
    assert not delta_rule.fits(inputs[0].shape[1], inputs[0].shape[-1], 128,
                               64, inputs[0].dtype)
    np.testing.assert_array_equal(delta_rule.rule(*inputs, 64),
                                  delta_rule.chunked(*inputs, 64))


# ------------- heads that are no whole lane tiles, beta up to 2 (PR 48)
# (appended: the cases above run on the schedule they had)


def _laid_inputs(windows, t, groups, dtype=jnp.float32, keys=None):
    """`_wide_inputs` as Olmo-Hybrid-7B's mixer hands them over: ONE value
    head a key head, widths 96 | 192, beta = 2 sigmoid(.)."""
    return _wide_inputs(windows, t, groups, dtype, keys=keys, d=96, e=192,
                        each=1, top=2.0)


@pytest.mark.parametrize("groups,windows,t,keys", [
    (30, 2, 64, None), (5, 1, 128, "one"), (6, 1, 128, None)],
    ids=["published_heads", "keys_align", "six_heads"])
def test_the_kernel_takes_heads_of_96_by_192_and_beta_up_to_2(
        groups, windows, t, keys):
    """`rule` at D = 96, E = 192, one value head a key head: the fused
    kernel pair (interpret mode here) on heads laid in 128 | 256 with zero
    columns, five (of 30, of 5) or four (of 6: `heads_a_step`) value
    heads a step, against `sequential` in float64: o and all five
    gradients, with beta drawn up to 2, at the published 30 heads x 2
    windows and where a chunk's keys all align (every entry of its system
    beta times a decay, up to 2: the case the product form loses as 2^63).
    1e-5 of the largest entry, as the kernel at whole tiles."""
    inputs, cot = _laid_inputs(windows, t, groups, keys=keys)
    assert float(inputs[-1].max()) > 1.8
    assert not delta_rule.fits(t, 96, 192, 64, jnp.float32)
    plan = delta_rule.plan(groups, t, 96, 192, 64, jnp.float32, heads=groups)
    assert plan == {"kernel": 1, "states_saved": 1, "padded_share": 0.4375,
                    "key_heads_a_step": 5 if groups % 5 == 0 else 6,
                    "value_heads_a_step": 5 if groups % 5 == 0 else 6}
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    assert got[0].shape == (windows, t, groups, 192)
    assert got[1].shape == (windows, t, groups, 96)
    want = _value_and_gradients(
        delta_rule.sequential,
        tuple(a.astype(jnp.float64) for a in inputs),
        cot.astype(jnp.float64))
    for name, a, r in zip(NAMES, got, want):
        assert a.shape == r.shape and np.isfinite(a).all(), name
        scale = float(jnp.max(jnp.abs(r)))
        assert scale > 0, name
        np.testing.assert_allclose(a, r, atol=1e-5 * max(scale, 1.0),
                                   err_msg=name)


def test_the_laid_heads_are_the_chunked_rule_in_bfloat16():
    """bfloat16 operands at 96 | 192: the kernel on the laid heads rounds
    as the `jax.numpy` form does at the widths as they are (a zero column
    adds nothing to any rounded sum)."""
    inputs, cot = _laid_inputs(1, 128, 5, jnp.bfloat16)
    got = _value_and_gradients(lambda *a: delta_rule.rule(*a, 64), inputs,
                               cot)
    same = _value_and_gradients(lambda *a: delta_rule.chunked(*a, 64),
                                inputs, cot)
    assert [a.dtype for a in got] == [jnp.float32] + 3 * [jnp.bfloat16] \
        + 2 * [jnp.float32]
    assert _gaps(got[:1], same[:1])[0] < 1e-4
    want = _value_and_gradients(_sequential32, inputs, cot)
    assert max(_gaps(got, want)) < 0.006, _gaps(got, want)


@pytest.mark.parametrize("groups,heads,held", [
    (16, 32, 2), (30, 30, 5), (6, 6, 6), (2, 4, 2), (1, 2, 1), (3, 3, 3),
    (8, 8, 4)])
def test_a_step_holds_the_fewest_key_heads_that_solve_four_in_step(
        groups, heads, held):
    """`heads_a_step`: Qwen3-Next's 2 of 16 (four value heads, the kernel
    text it had: `key_heads_a_step` says the same), 5 of 30 at one value
    head a key head; all of G where no divisor reaches `IN_STEP`."""
    assert delta_rule.IN_STEP == 4
    assert delta_rule.heads_a_step(groups, heads) == held
    assert groups % held == 0
    if heads == 2 * groups:
        assert held == delta_rule.key_heads_a_step(groups)


@pytest.mark.parametrize("d,e,share,kernel", [
    (128, 128, 0.0, 1), (96, 192, 0.4375, 1), (128, 192, 0.25, 1),
    (64, 128, 0.5, 0), (8, 8, 0.99609375, 0)])
def test_the_zero_columns_are_laid_only_where_they_are_the_lesser_part(
        d, e, share, kernel):
    """`padded_share` of the kernel's state products, and `plan`: the
    kernel at whole tiles and where under half of its products would
    multiply zeros; a head of half a tile or less stays `chunked`'s."""
    assert delta_rule.padded_share(d, e) == share
    plan = delta_rule.plan(4, 128, d, e, 64, jnp.bfloat16, heads=4)
    assert plan["kernel"] == kernel
    assert plan["padded_share"] == (share if kernel else 0.0)
    assert plan["value_heads_a_step"] == (4 if kernel else 0)
