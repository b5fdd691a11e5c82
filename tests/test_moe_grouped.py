"""ops/grouped_matmul.py (the kernel interpreted, on the CPU) against
`jax.lax.ragged_dot`, and ops/moe.py's two sides of its dispatch against
each other at a layer the kernel takes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.ops import grouped_matmul as gm
from biscotti_tpu.ops import moe

TILE, E = 128, 4


def _ragged(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.float32)


CASES = {  # name: (rows of the buffer, the four groups' sizes)
    "an_empty_group": (256, [100, 0, 156, 0]),
    "a_group_over_three_tiles": (512, [60, 300, 100, 52]),
    "a_group_of_one_row": (256, [1, 200, 1, 54]),
    "rows_in_no_group": (512, [50, 70, 0, 30]),
    "rows_in_no_group_from_a_tile_start": (512, [128, 0, 100, 28]),
    "all_rows_in_one_group": (256, [0, 256, 0, 0]),
    "no_row_in_any_group": (256, [0, 0, 0, 0]),
}


@pytest.mark.parametrize("k,n", [(128, 256), (256, 128)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ragged_dot_and_its_gradient(case, k, n):
    """The product and the rows' cotangent at 1e-5 of `ragged_dot`'s, in
    float32; the rows past the groups exact zeros, forward and backward."""
    c, sizes = CASES[case]
    rng = np.random.default_rng(c + k)
    xs = jnp.asarray(rng.normal(size=(c, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, k, n)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(c, n)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    assert gm.column_tile(c, k, n, w.dtype, TILE) == n
    got, back = jax.vjp(lambda x: gm.grouped(x, w, sizes, TILE), xs)
    want, back_want = jax.vjp(lambda x: _ragged(x, w, sizes), xs)
    assert got.dtype == jnp.float32 and got.shape == (c, n)
    for mine, theirs in ((got, want), (back(ct)[0], back_want(ct)[0])):
        scale = float(jnp.max(jnp.abs(theirs))) or 1.0
        np.testing.assert_allclose(mine, theirs, atol=1e-5 * scale, rtol=0)
        assert not np.asarray(mine[int(sizes.sum()):]).any()
    # a walk in row tiles visits every tile a group has rows in
    ends = np.cumsum(sizes)
    visits = sum(len(range(lo // TILE, (hi - 1) // TILE + 1))
                 for lo, hi in zip(ends - np.asarray(sizes), ends) if hi > lo)
    assert int(gm.tile_visits(sizes, TILE)) == visits


def test_the_tile_follows_the_rows_a_group_and_the_shape_picks_the_side():
    assert [gm.row_tile(r) for r in (1, 120, 128, 129, 256, 300, 4000)] \
        == [128, 128, 128, 256, 256, 512, 512]
    bf16 = jnp.bfloat16
    # the widest column tile whose buffers stay under the compiler's
    # default scoped VMEM: half the width where K is 3,072
    assert gm.column_tile(15360, 3072, 1024, bf16, 128) == 512
    assert gm.column_tile(30720, 1024, 3072, bf16, 128) == 1024
    for shape in ((15360 + 8, 3072, 1024), (15360, 3072 + 8, 1024),
                  (15360, 3072, 1000), (96, 32, 8)):
        assert gm.column_tile(*shape, bf16, 128) is None, shape
    assert gm.column_tile(15360, 3072, 1024, jnp.float16, 128) is None
    assert gm.column_tile(1024, 1 << 20, 1024, bf16, 128) is None  # VMEM
    # the tiny model of the CPU tests stays with the compiler
    assert moe._plan((24, 96), 32, 8, jnp.float32, 6.0) == 0
    assert moe._plan((15360, 30720), 3072, 1024, bf16, 120.0) == 128


def _layer(seed, crowded):
    """A layer the kernel takes: hidden and width 128, 4 of 16 experts
    held, 128 tokens x 4: a cut buffer of 256 rows, an uncut one of 512."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (128, 128), jnp.float32)
    weights = {name: jax.random.normal(key, (E, 128, 128), jnp.float32) / 11
               for name, key in zip(("w_gate", "w_up", "w_down"), keys[1:])}
    router = jax.random.normal(keys[4], (128, 16), jnp.float32) / 11
    experts, coef, _ = moe.route(x, router, 4, 2.5)
    if crowded:  # every token picks the four held experts: the uncut side
        experts = jnp.tile(jnp.arange(E, dtype=jnp.int32), (128, 1))
    return x, experts, coef, weights


def _held(x, experts, coef, weights):
    # under the `jit` of `held_experts`: its cache cannot see `_plan`
    return moe.held_experts.__wrapped__(x, experts, coef, weights, 0, 16)


@pytest.mark.parametrize("remat", [False, True],
                         ids=["grad", "checkpoint_grad"])
@pytest.mark.parametrize("crowded", [False, True],
                         ids=["the_cut_buffer", "the_uncut_buffer"])
def test_the_layer_is_the_same_on_both_sides_of_the_dispatch(crowded, remat,
                                                             monkeypatch):
    x, experts, coef, weights = _layer(3, crowded)

    def run(x, coef):
        # as models/lm.py's decoder has it: the layer rematerialised (a
        # function of this trace: `checkpoint` keeps what it traced)
        layer = jax.checkpoint(lambda *a: _held(*a)) if remat else _held
        out, counts = layer(x, experts, coef, weights)
        return jnp.sum(out * jnp.cos(out)), (out, counts)

    grad = jax.jit(jax.value_and_grad(run, argnums=(0, 1), has_aux=True))
    (_, (out, counts)), (dx, dcoef) = grad(x, coef)
    assert int(counts["grouped_kernel"]) == 1
    assert int(counts["dropped"]) == 0
    held = int(counts["load"].sum())
    assert held == 512 if crowded else 0 < held <= 256
    # the mechanism's counter: which buffer the call ran on
    assert int(counts["buffer_rows"]) == 256 == moe.CAPACITY * 128 * 4 / 16 * E
    assert int(counts["uncut"]) == int(crowded)
    assert int(counts["tile_rows"]) == TILE * int(
        gm.tile_visits(counts["load"], TILE)) >= held
    monkeypatch.setattr(moe, "_plan", lambda *a: 0)
    (_, (out_c, counts_c)), (dx_c, dcoef_c) = jax.jit(jax.value_and_grad(
        run, argnums=(0, 1), has_aux=True))(x, coef)
    assert int(counts_c["grouped_kernel"]) == 0
    assert int(counts_c["tile_rows"]) == gm.COMPILER_ROW_TILE * int(
        gm.tile_visits(counts["load"], gm.COMPILER_ROW_TILE))
    np.testing.assert_array_equal(counts["load"], counts_c["load"])
    for mine, theirs in ((out, out_c), (dx, dx_c), (dcoef, dcoef_c)):
        scale = float(jnp.max(jnp.abs(theirs)))
        assert scale > 0
        np.testing.assert_allclose(mine, theirs, atol=1e-5 * scale, rtol=0)


def _products(jaxpr, found):
    """Grouped products in `jaxpr` and every jaxpr nested in it, by the
    rows of their result: {rows: count}. The kernel's are `pallas_call`s,
    one a platform `_run` lowers for (`platform_dependent`): the chip's."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "ragged_dot_general" or (
                name == "pallas_call" and not eqn.params["interpret"]):
            rows = eqn.outvars[0].aval.shape[0]
            found[rows] = found.get(rows, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _products(sub, found)
    return found


@pytest.mark.parametrize("side", ["kernel", "compiler"])
def test_a_rematerialised_layers_gradient_has_nine_products_a_side(
        side, monkeypatch):
    """Under `jax.checkpoint` and `jax.grad` (models/lm.py's decoder) a
    sparse layer costs 9 grouped products on the buffer it runs on: the
    primal's 3 and, in the backward's ONE branch, 3 recomputed and 3
    transposed. The layer's recomputed forward feeds nothing (the experts'
    result enters the layer's output by a sum) and is gone from the
    program: 12 would be its three kept."""
    if side == "compiler":
        monkeypatch.setattr(moe, "_plan", lambda *a: 0)
    x, experts, coef, weights = _layer(3, False)

    def loss(x, coef):
        out = jax.checkpoint(lambda x, coef: x + _held(
            x, experts, coef, weights)[0])(x, coef)
        return jnp.sum(out * jnp.cos(out))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, coef)
    assert _products(jaxpr.jaxpr, {}) == {256: 9, 512: 9}
    # the choice is made twice, and what the backward's hands out is the
    # two cotangents: no residual crosses it
    choices = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "cond":
                sides = [_products(b.jaxpr, {}) for b in
                         eqn.params["branches"]]
                if sorted(map(sorted, sides)) == [[256], [512]]:
                    choices.append(([v.aval.shape for v in eqn.outvars],
                                    sorted(n for s in sides
                                           for n in s.values())))
                    continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert sorted(choices) == [([(128, 128)], [3, 3]),
                               ([(128, 128), (128, 4)], [6, 6])]


@pytest.mark.parametrize("model", ["laguna_tiny", "deepseek_v2_tiny"])
def test_a_round_counts_its_uncut_calls_and_its_buffers_rows(model):
    """Both models make the same call: a tiny round of each, its four
    sampled peers walked in two blocks, runs every (block, sparse layer)
    call on a sorted buffer of CAPACITY x the uniform router's rows and
    says so (`dispatch_stats`, the two gauges)."""
    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator
    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry()
    sim = Simulator(BiscottiConfig(
        dataset="lm_tokens_tiny", model_name=model, num_nodes=6,
        batch_size=2, epsilon=1.0, noising=True, verification=True,
        defense=Defense.KRUM, sample_percent=1.0, num_verifiers=1,
        num_miners=1, num_noisers=1, learning_rate=0.1, grad_clip=0.05,
        seed=9), metrics=registry)
    sim.steps.block = 2  # before the round is traced
    sim.run(num_rounds=1, stop_at_convergence=False)
    cfg = sim.model.info["config"]
    assert (cfg.num_experts, cfg.experts_held, cfg.top_k) == (16, 4, 3)
    tokens = 2 * 2 * 16  # a block: 2 peers x 2 windows of 16
    rows = moe.CAPACITY * tokens * 3 / 16 * 4
    assert rows == 96 < tokens * 3
    counts = sim.last_counts  # a sparse layer, summed over the two blocks
    np.testing.assert_array_equal(counts["buffer_rows"], [2 * rows] * 2)
    np.testing.assert_array_equal(counts["uncut"], [0, 0])
    stats = sim.dispatch_stats()
    assert stats["buffer_rows"] == rows and stats["uncut_calls"] == 0
    assert stats["tokens_dropped"] == 0
    page = registry.render()
    assert "biscotti_moe_uncut_calls 0" in page
    assert "biscotti_moe_buffer_rows 96" in page
