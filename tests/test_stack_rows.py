"""The round reads only the rows it trains on (PR 25): the composed gather
against the two-step gather taken by hand, the stack's layout rule, and the
witness that reads the compiled program for whole-stack passes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.models.peer_step import (STACK_PAD_LIMIT, put_stack,
                                           stack_layout)
from biscotti_tpu.models.trainer import (local_step_fn, sample_batch,
                                         step_rule)
from biscotti_tpu.parallel import sim as sim_mod
from biscotti_tpu.parallel.sim import (Simulator, make_sharded_round_step,
                                       whole_stack_instructions)
from biscotti_tpu.telemetry import MetricsRegistry

CASES = {
    "softmax": dict(dataset="mnist", model_name="softmax"),
    "cnn": dict(dataset="mnist", model_name="mnist_cnn"),
    "creditcard": dict(dataset="creditcard"),  # d = 24: the default layout
}


def _cfg(**kw):
    base = dict(num_nodes=12, sample_percent=0.7, batch_size=10, epsilon=1.0,
                noising=True, verification=True, defense=Defense.KRUM,
                seed=11)
    return BiscottiConfig(**{**base, **kw})


@pytest.fixture(scope="module", params=sorted(CASES))
def case(request):
    """(sim, w, it, what the program returned, the same taken by hand)."""
    sim = Simulator(_cfg(**CASES[request.param]))
    it = 3
    w = jnp.asarray(np.random.default_rng(5).normal(
        0.0, 0.05, sim.num_params), jnp.float32)
    seed = jnp.asarray(sim.cfg.seed, jnp.int32)
    got = sim._noised_jit(w, it, seed, sim.x, sim.y)

    # by hand, the way the round was written before: the stated streams,
    # x[cidx] whole, then each peer's xi[idx]
    rkey = jax.random.fold_in(
        jax.random.fold_in(jax.random.PRNGKey(0), seed), it)
    ckey, bkey, nkey = jax.random.split(rkey, 3)
    cidx = sim._contributors(ckey)
    xs, ys = np.asarray(sim.x)[np.asarray(cidx)], \
        np.asarray(sim.y)[np.asarray(cidx)]
    idx = [np.asarray(sample_batch(jax.random.fold_in(bkey, i), sim.rows,
                                   sim.cfg.batch_size)) for i in cidx]
    xb = np.stack([xi[j] for xi, j in zip(xs, idx)])
    yb = np.stack([yi[j] for yi, j in zip(ys, idx)])

    step = local_step_fn(sim.model, sim.mode, clip=sim.cfg.grad_clip,
                         alpha=step_rule(sim.model, sim.cfg)[1])

    @jax.jit
    def by_hand(w, xb, yb, cidx):
        deltas = jax.vmap(step, in_axes=(None, 0, 0))(w, xb, yb)
        noise = jax.vmap(lambda i: sim._peer_noise(
            jax.random.fold_in(nkey, i)))(cidx)
        return deltas, deltas + noise  # one program: one rounding of a*b+c

    return sim, w, it, got, (cidx,) + by_hand(w, xb, yb, cidx)


def test_composed_gather_samples_the_same_contributors(case):
    _, _, _, (cidx, _, _), (want, _, _) = case
    assert cidx.shape[0] == 6  # 70% of 12, less the committees
    np.testing.assert_array_equal(np.asarray(cidx), np.asarray(want))


def test_composed_gather_gives_bit_equal_raw_deltas(case):
    _, _, _, (_, deltas, _), (_, want, _) = case
    assert float(jnp.max(jnp.abs(want))) > 0.0
    np.testing.assert_array_equal(np.asarray(deltas), np.asarray(want))


def test_composed_gather_gives_bit_equal_noised_deltas(case):
    sim, w, it, (_, deltas, noised), (_, _, want) = case
    assert not np.array_equal(np.asarray(noised), np.asarray(deltas))
    np.testing.assert_array_equal(np.asarray(noised), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(sim.noised_updates(w, it)),
                                  np.asarray(want))


def test_minibatches_are_the_rows_by_hand_wherever_the_shards_sit():
    """The sharded step's call: keys from the global ids, rows from the
    local positions."""
    sim = Simulator(_cfg(**CASES["softmax"]))
    bkey = jax.random.PRNGKey(8)
    ids, at = jnp.asarray([7, 2, 5]), jnp.asarray([0, 3, 1])
    bkeys = jax.vmap(lambda i: jax.random.fold_in(bkey, i))(ids)
    xb, yb = jax.jit(sim.steps.minibatches)(bkeys, at, sim.x, sim.y)
    assert xb.shape == (3, 10, 784) and yb.shape == (3, 10)
    for k in range(3):
        idx = np.asarray(sample_batch(jax.random.fold_in(bkey, ids[k]),
                                      sim.rows, 10))
        np.testing.assert_array_equal(np.asarray(xb[k]),
                                      np.asarray(sim.x)[int(at[k])][idx])
        np.testing.assert_array_equal(np.asarray(yb[k]),
                                      np.asarray(sim.y)[int(at[k])][idx])


def test_sharded_step_equals_the_same_step_on_one_device():
    """One body, two programs: eight devices and one give the same round."""
    if len(jax.devices()) < 8:
        pytest.skip("needs the eight virtual devices of conftest")
    sim = Simulator(_cfg(num_nodes=8, sample_percent=1.0,
                         **CASES["softmax"]))
    many = jax.sharding.Mesh(np.array(jax.devices()[:8]), ("peers",))
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("peers",))
    w = jnp.asarray(np.random.default_rng(2).normal(
        0.0, 0.05, sim.num_params), jnp.float32)
    step_n = make_sharded_round_step(sim, many)
    assert len({s.device for s in step_n.x.addressable_shards}) == 8
    w_n, mask_n, _ = step_n(w, 1)
    w_1, mask_1, _ = make_sharded_round_step(sim, one)(w, 1)
    np.testing.assert_array_equal(np.asarray(mask_n), np.asarray(mask_1))
    np.testing.assert_allclose(np.asarray(w_n), np.asarray(w_1), atol=2e-6)


# ------------------------------------------------------------ the layout


@pytest.mark.parametrize("shape, row_major", [
    ((3383, 480, 784), True),    # 896 / 784 = 1.14: the benchmark's cell
    ((3383, 480), True),         # its labels: 512 / 480 over 3384 / 3383
    ((100, 400, 3072), True),    # cifar: no padding at all
    ((100, 160, 8742), True),    # lfw: 8832 / 8742 = 1.01
    ((10, 320, 24), None),       # creditcard: 128 / 24 = 5.3
    ((10, 112, 64), None),       # digits: 2.0
    ((12, 480), None),           # 16 x 512 over 12 x 480 = 1.42
    ((480,), None), ((0, 480, 784), None),
])
def test_stack_layout_is_decided_by_the_padding_of_the_shape(shape,
                                                             row_major):
    layout = stack_layout(shape)
    if row_major is None:
        assert layout is None
    else:
        assert layout.major_to_minor == tuple(range(len(shape)))
    assert 1.14 < STACK_PAD_LIMIT < 2.0


def test_put_stack_changes_nothing_where_row_major_is_the_default():
    """The CPU: same placement, same layout, still uncommitted."""
    x = np.arange(4 * 16 * 784, dtype=np.float32).reshape(4, 16, 784)
    assert stack_layout(x.shape) is not None
    plain, put = jnp.asarray(x), put_stack(x)
    assert str(put.format) == str(plain.format)
    assert put.sharding == plain.sharding
    assert put._committed == plain._committed
    np.testing.assert_array_equal(np.asarray(put), x)
    assert put_stack(plain) is plain


def test_put_stack_places_on_a_mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual devices of conftest")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("peers",))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("peers"))
    x = np.arange(8 * 16 * 784, dtype=np.float32).reshape(8, 16, 784)
    put = put_stack(jnp.asarray(x), sharding)
    assert put.sharding == sharding
    assert put.addressable_shards[0].data.shape == (2, 16, 784)
    np.testing.assert_array_equal(np.asarray(put), x)


@pytest.fixture(scope="module")
def small():
    return Simulator(_cfg(**CASES["softmax"]))


def test_simulator_holds_its_stack_as_put_stack_leaves_it(small):
    assert small.x.shape == (12, 480, 784) and small.x.dtype == jnp.float32
    assert small.y.shape == (12, 480)
    assert str(small.x.format) == str(jnp.asarray(np.asarray(small.x)).format)


def test_stack_info_reads_layout_and_bytes(small):
    info = small.stack_info()
    assert info["layout"] == "2,1,0" and info["row_major"] is True
    assert info["compact_bytes"] == 12 * 480 * 784 * 4
    assert info["device_bytes"] == info["compact_bytes"]  # the CPU pads none


def test_run_exports_the_stack_gauge():
    registry = MetricsRegistry()
    sim = Simulator(_cfg(**CASES["softmax"]), metrics=registry)
    sim.run(1)
    gauge = registry.gauge("biscotti_sim_stack_bytes")
    assert gauge.value(layout="2,1,0") == 12 * 480 * 784 * 4
    assert 'biscotti_sim_stack_bytes{layout="2,1,0"}' in registry.render()


# ------------------------------------------------------------ round_hlo


def _entry_layouts(hlo):
    line = next(l for l in hlo.splitlines()
                if "entry_computation_layout" in l)
    return line[line.index("entry_computation_layout"):]


def test_round_hlo_lowers_with_the_formats_of_its_arguments(small,
                                                            monkeypatch):
    seen = []
    real = jax.ShapeDtypeStruct

    def spy(*args, **kw):
        seen.append(kw.get("sharding"))
        return real(*args, **kw)

    monkeypatch.setattr(sim_mod.jax, "ShapeDtypeStruct", spy)
    hlo = small.round_hlo()
    assert small.x.format in seen and small.y.format in seen
    assert "f32[12,480,784]{2,1,0}" in _entry_layouts(hlo)
    assert "s32[12,480]{1,0}" in _entry_layouts(hlo)


def test_round_program_has_no_whole_stack_instruction(small):
    hlo = small.round_hlo()
    assert small.whole_stack_instructions(hlo) == []
    assert small.whole_stack_instructions() == []  # compiles it itself


# ---------------------------------------------------------- the witness

HLO = """HloModule jit_round_step, entry_computation_layout={(f32[3383,480,784]{0,2,1:T(8,128)})->f32[7850]{0}}

%fused_computation.1 (param_0.5: f32[3383,480,784]) -> bf16[23680,784] {
  %param_0.5 = f32[3383,480,784]{2,1,0:T(8,128)} parameter(0)
  %convert.9 = bf16[3383,480,784]{2,1,0:T(8,128)(2,1)} convert(%param_0.5)
  ROOT %gather.5 = bf16[23680,784]{1,0:T(8,128)(2,1)} gather(%convert.9)
}

ENTRY %main.27 (x.1: f32[3383,480,784], y.1: s32[3383,480]) -> f32[7850] {
  %x.1 = f32[3383,480,784]{0,2,1:T(8,128)} parameter(0), metadata={op_name="x"}
  %y.1 = s32[3383,480]{0,1:T(8,128)} parameter(1)
  %bitcast.17 = f32[1623840,784]{1,0:T(8,128)} bitcast(%x.1)
  %copy.25 = s32[3383,480]{1,0:T(8,128)S(1)} copy(%y.1)
  %slice-start = ((s32[3383,480]{1,0:T(8,128)}), s32[848,480]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%y.1)
  %mini-gather-slice = bf16[3383,480,512]{0,2,1:T(8,128)(2,1)} slice(%x.1), slice={[0:3383], [0:480], [0:512]}
  %copy.69 = bf16[1623840,272]{1,0:T(8,128)(2,1)} copy(%bitcast.17)
  %both = (bf16[3383,480,784]{2,1,0}, s32[]) fusion(%x.1), kind=kLoop, calls=%fused_computation.2
  %fusion.1 = bf16[23680,784]{1,0:T(8,128)(2,1)S(1)} fusion(%x.1), kind=kCustom, calls=%fused_computation.1
  ROOT %dot.3 = f32[7850]{0:T(1024)} dot(%fusion.1, %fusion.1)
}
"""


def test_witness_names_what_spans_the_stack_and_nothing_else():
    found = whole_stack_instructions(HLO, 3383, 480)
    assert [f.split(" = ")[0] for f in found] == [
        "mini-gather-slice", "copy.69", "both"]
    assert found[0].endswith(" slice") and "bf16[3383,480,512]" in found[0]


def test_witness_reads_one_devices_share_of_a_sharded_program():
    share = HLO.replace("3383", "256").replace("1623840", "122880")
    assert len(whole_stack_instructions(share, 256, 480)) == 3
    assert whole_stack_instructions(share, 1024, 480) == []
