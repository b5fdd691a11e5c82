"""ops/attention.py's fused, blocked attention core (interpreted: the CPU has
no Mosaic) against the `einsum` form it replaces, at shapes that meet the
kernel's rule: heads of 128, windows of whole blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.ops import attention as at

D = 128


def _inputs(dtype, g, t, windows=1, kv=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (windows, kv, g, t, D), jnp.float32)
    k, v = (jax.random.normal(key, (windows, kv, t, D), jnp.float32)
            for key in keys[1:3])
    cot = jax.random.normal(keys[3], q.shape, jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), cot


def _both_passes(form, q, k, v, cot):
    out, back = jax.vjp(form, q, k, v)
    return (out,) + back(cot)


# (query block, key block): several key blocks a query block, the two sizes
# apart, so that the running maximum, the visited range and the rows a
# block hides entirely are all on the path
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("g", [6, 9])
@pytest.mark.parametrize("window,block", [(256, (128, 128)),   # causal
                                          (100, (128, 128)),
                                          (160, (256, 128))])
def test_the_kernel_is_the_einsum_form_and_its_gradients(dtype, tol, g,
                                                         window, block):
    q, k, v, cot = _inputs(jnp.dtype(dtype), g, 256)
    want = _both_passes(lambda *a: at.plain(*a, window), q, k, v, cot)
    got = _both_passes(lambda *a: at.fused(*a, window, block), q, k, v, cot)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # the operands' rounding where they are bfloat16: relative to the
        # array's own size
        assert np.max(np.abs(a - b)) <= tol * np.max(np.abs(b)), name
    assert got[0].dtype == jnp.float32  # the result is not rounded


@pytest.mark.parametrize("t,window,bq,bk", [
    (1024, 512, 128, 128), (1024, 512, 256, 256), (1024, 512, 512, 512),
    (1024, 512, 256, 128), (1024, 512, 128, 256), (1024, 1024, 256, 256),
    (1024, 1, 128, 128), (512, 200, 128, 128), (512, 129, 256, 128),
    (256, 300, 128, 128)])
def test_the_visited_pairs_are_those_with_a_key_that_is_seen(t, window, bq,
                                                             bk):
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & (i - j < window)
    tiles = seen.reshape(t // bq, bq, t // bk, bk).any(axis=(1, 3))
    want = [(int(a), int(b)) for a, b in zip(*np.nonzero(tiles))]
    assert at.visited(t, window, bq, bk) == want
    assert at.block_share(t, window, bq, bk) == len(want) / tiles.size


def test_the_published_layers_take_the_first_blocks_and_skip_a_quarter():
    """Key blocks of 512 measured fastest on the chip (PERF.md section 6,
    PR 30): the walk's cost a step outweighs the pairs a finer block would
    skip."""
    for g in (6, 9):
        assert at.blocks(g, 1024, D, jnp.bfloat16) == at.BLOCKS[0] == (256,
                                                                       512)
    assert at.block_share(1024, 512, 256, 512) == 0.75
    assert at.block_share(1024, 1024, 256, 512) == 0.75
    assert at.block_share(1024, 512, 128, 128) == 30 / 64
    # a shorter window takes the first pair that divides it
    assert at.blocks(6, 256, D, jnp.bfloat16) == (256, 256)
    assert at.blocks(6, 128, D, jnp.float32) == (128, 128)


@pytest.mark.parametrize("why,g,t,d,dtype", [
    ("the tiny preset's heads", 2, 16, 8, "float32"),
    ("a head size not of 64", 6, 256, 32, "bfloat16"),
    ("a window that is no whole number of blocks", 6, 200, 128, "bfloat16"),
    ("another type", 6, 256, 128, "float16"),
    ("a key/value head too long to hold whole", 9, 1 << 16, 128, "float32")])
def test_shapes_the_kernel_does_not_take_run_the_einsum_form(why, g, t, d,
                                                             dtype):
    assert at.blocks(g, t, d, jnp.dtype(dtype)) is None, why
    if t <= 256:
        shape = jax.ShapeDtypeStruct((1, 1, g, t, d), jnp.dtype(dtype))
        kv = jax.ShapeDtypeStruct((1, 1, t, d), jnp.dtype(dtype))
        text = str(jax.make_jaxpr(lambda *a: at.attention(*a, 4))(
            shape, kv, kv))
        assert "pallas_call" not in text and "dot_general" in text


def _made(jaxpr, found):
    """(primitive, shape) of every array the program makes OUTSIDE its
    kernels (a kernel's tiles live in VMEM)."""
    for eqn in jaxpr.eqns:
        found += [(eqn.primitive.name, tuple(var.aval.shape))
                  for var in eqn.outvars if hasattr(var.aval, "shape")]
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                _made(sub, found)
    return found


def test_shapes_the_kernel_takes_run_it_and_hold_no_score():
    q, k, v, _ = _inputs(jnp.bfloat16, 6, 256)
    made = _made(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(at.attention(*a, 100)), argnums=(0, 1, 2)))(
            q, k, v).jaxpr, [])
    # forward, and ONE backward; each twice: `platform_dependent` traces
    # the interpreted side and Mosaic's
    assert sum(name == "pallas_call" for name, _ in made) == 2 * (2 + 3)
    assert not [shape for _, shape in made if shape[-2:] == (256, 256)]
    # and the residuals are q, k, v, the result and the rows' log-sum-exp
    assert (1, 1, 6, 1, 256) in {shape for _, shape in made}


@pytest.mark.parametrize("window", [256, 257, 10**6])
def test_a_window_of_the_whole_length_or_more_is_the_causal_mask(window):
    q, k, v, cot = _inputs(jnp.float32, 2, 256)
    causal = _both_passes(lambda *a: at.fused(*a, 256, (128, 128)),
                          q, k, v, cot)
    got = _both_passes(lambda *a: at.fused(*a, window, (128, 128)),
                       q, k, v, cot)
    for a, b in zip(got, causal):
        np.testing.assert_array_equal(a, b)
    i, j = np.arange(256)[:, None], np.arange(256)[None, :]
    scores = jnp.einsum("wgqtd,wgsd->wgqts", q, k) / np.sqrt(D)
    probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(got[0],
                               jnp.einsum("wgqts,wgsd->wgqtd", probs, v),
                               atol=2e-5)


@pytest.mark.parametrize("window", [100, 256])
def test_two_windows_in_one_call_are_each_alone(window):
    q, k, v, cot = _inputs(jnp.float32, 3, 256, windows=2, kv=2)
    both = _both_passes(lambda *a: at.fused(*a, window, (128, 128)),
                        q, k, v, cot)
    for w in range(2):
        alone = _both_passes(lambda *a: at.fused(*a, window, (128, 128)),
                             q[w:w + 1], k[w:w + 1], v[w:w + 1],
                             cot[w:w + 1])
        for a, b in zip(both, alone):
            np.testing.assert_array_equal(a[w:w + 1], b)
    # nor do two key/value heads of a window
    assert not np.allclose(both[0][:, 0], both[0][:, 1])


def test_a_row_whose_first_visited_block_hides_every_key_is_finite():
    """Window 129 at blocks of 128: the last row of query block 1 sees keys
    127.. only, one key of its first visited block; window 128, none: the
    block is still visited for the rows above it."""
    q, k, v, cot = _inputs(jnp.float32, 2, 256)
    for window in (128, 129):
        got = _both_passes(lambda *a: at.fused(*a, window, (128, 128)),
                           q, k, v, cot)
        want = _both_passes(lambda *a: at.plain(*a, window), q, k, v, cot)
        for a, b in zip(got, want):
            assert np.isfinite(a).all()
            np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_heads_of_64_with_their_own_scale_run_the_kernel(dtype, tol):
    """Granite-4.0-H's attention (PR 33): four query heads a key/value head,
    scores over 64 and values of 64 (half a lane tile each, as they are),
    the scores times 1 / 64 and not 1 / sqrt(64): `blocks` takes the shape
    and the kernel's forward and backward are the `einsum` form's."""
    dtype = jnp.dtype(dtype)
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(keys[0], (2, 2, 4, 256, 64), jnp.float32)
    k, v = (jax.random.normal(key, (2, 2, 256, 64), jnp.float32)
            for key in keys[1:3])
    cot = jax.random.normal(keys[3], q.shape, jnp.float32)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    assert at.blocks(4, 256, 64, dtype, 64) == (256, 256)
    assert at.blocks(4, 1024, 64, jnp.bfloat16, 64) == (256, 512)
    text = str(jax.make_jaxpr(lambda *a: at.attention(*a, 256, 1 / 64))(
        q, k, v))
    assert "pallas_call" in text
    got = _both_passes(lambda *a: at.attention(*a, 256, 1 / 64), q, k, v,
                       cot)
    want = _both_passes(lambda *a: at.plain(*a, 256, 1 / 64), q, k, v, cot)
    for a, b in zip(got, want):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max())
    other = at.plain(q, k, v, 256)  # 1 / sqrt(64): another result
    assert float(jnp.max(jnp.abs(other - want[0]))) > 0.05
