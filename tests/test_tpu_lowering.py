"""Ahead-of-time compilation for the TPU v5e, from the CPU, WITH x64 on.

The cheap pre-flight before spending chip time: libtpu can describe a
`v5e:2x2` topology without a chip, and `jit(...).lower(...).compile()`
against its devices runs the real TPU compiler (Mosaic included). Every
live entry point enables `jax_enable_x64`, and the rest of tier-1 can only
run Pallas kernels in interpret mode, where int64 is legal — so a kernel
that cannot lower for the chip under x64 is invisible to it. Here it fails.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from jax.experimental.layout import Format

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.models.peer_step import stack_layout
from biscotti_tpu.models.trainer import local_step_fn, sample_batch
from biscotti_tpu.ops.krum_pallas import krum_scores_pallas
from biscotti_tpu.parallel.sim import (Simulator, sharded_round_step_fn,
                                       whole_stack_instructions)


@pytest.fixture(scope="module")
def v5e():
    """The four devices of a v5e 2x2 host, as a compile target."""
    assert jax.config.jax_enable_x64, "conftest turns x64 on; so does main()"
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot build topologies
        reason = f"libtpu cannot build a v5e:2x2 topology: {e}"
        print(reason)
        pytest.skip(reason)
    assert len(topo.devices) == 4
    return topo.devices


def _abstract(arrays, sharding, stack=False):
    """Shapes on `sharding`; with `stack`, in the format `put_stack` gives a
    peer stack on the chip (a described device can hold no array to ask)."""

    def where(a):
        layout = stack_layout(a.shape, a.dtype.itemsize) if stack else None
        return sharding if layout is None else Format(layout, sharding)

    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where(a))
            for a in arrays]


def _compile_round_step(sim, device, stack=True, step=None):
    w, stake = sim.init_state()
    one = SingleDeviceSharding(device)
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim.cfg.seed, jnp.int32)], one)
            + _abstract([sim.x, sim.y], one, stack=stack)
            + _abstract([sim.x_val, sim.y_val], one))
    return jax.jit(step or sim._round_step_raw).lower(*args).compile()


def _compile_sharded_step(sim, devices, stack=True):
    mesh = jax.sharding.Mesh(np.array(devices), ("peers",))
    rep, peers = NamedSharding(mesh, P()), NamedSharding(mesh, P("peers"))
    w = jnp.zeros((sim.num_params,), jnp.float32)
    args = (_abstract([w], rep)
            + _abstract([sim.x, sim.y], peers, stack=stack)
            + _abstract([jnp.asarray(0),
                         jnp.asarray(sim.cfg.seed, jnp.int32)], rep)
            + [sim.frozen])  # `{}`: a classifier holds no frozen tree
    return sharded_round_step_fn(sim, mesh).lower(*args).compile()


def _cfg(**kw):
    base = dict(batch_size=10, epsilon=1.0, noising=True, verification=True,
                defense=Defense.KRUM, seed=0)
    return BiscottiConfig(**{**base, **kw})


def test_pallas_krum_lowers_through_mosaic_under_x64(v5e):
    """ops/krum_pallas.py at the bottom of its window. Without the x64
    guard around the pallas_call this dies in Mosaic lowering."""
    x = jax.ShapeDtypeStruct((512, 7850), jnp.float32,
                             sharding=SingleDeviceSharding(v5e[0]))
    compiled = krum_scores_pallas.lower(x, 256).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_creditcard_round_step_compiles_for_v5e(v5e):
    sim = Simulator(_cfg(dataset="creditcard", num_nodes=10,
                         sample_percent=0.70))
    _compile_round_step(sim, v5e[0])


def test_sharded_round_step_compiles_for_four_chips(v5e):
    sim = Simulator(_cfg(dataset="creditcard", num_nodes=8,
                         sample_percent=1.0))
    hlo = _compile_sharded_step(sim, v5e).as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo


@pytest.mark.slow
def test_mnist_cnn_100_compiles_for_v5e(v5e):
    """chip_smoke.py's device round and multi-chip shapes."""
    sim = Simulator(_cfg(dataset="mnist", model_name="mnist_cnn",
                         num_nodes=100, sample_percent=0.70))
    _compile_round_step(sim, v5e[0])
    sim = Simulator(_cfg(dataset="mnist", model_name="mnist_cnn",
                         num_nodes=100, sample_percent=1.0))
    _compile_sharded_step(sim, v5e)


@pytest.mark.slow
def test_pallas_round_at_1024_peers_compiles_for_v5e(v5e):
    """716 contributors: the round step with the Mosaic kernel inside."""
    sim = Simulator(_cfg(dataset="mnist", num_nodes=1024,
                         sample_percent=0.70))
    assert "tpu_custom_call" in _compile_round_step(sim, v5e[0]).as_text()


# ------------------------------------- the round reads no whole stack (PR 25)


@pytest.fixture(scope="module")
def sim_1024():
    """1,024 peers of mnist (1.5 GB of shards): large enough for the
    compiler to decide as it does at the benchmark's 3,383."""
    return Simulator(_cfg(dataset="mnist", num_nodes=1024,
                          sample_percent=0.70))


def _x_entry_layout(hlo):
    line = next(l for l in hlo.splitlines()
                if "entry_computation_layout" in l)
    return line[line.index("480,784]") + len("480,784]"):][:20]


def test_round_at_1024_peers_reads_no_whole_stack_on_one_chip(v5e, sim_1024):
    hlo = _compile_round_step(sim_1024, v5e[0]).as_text()
    assert "tpu_custom_call" in hlo
    assert _x_entry_layout(hlo).startswith("{2,1,0:T(8,128)}")
    assert whole_stack_instructions(hlo, 1024, sim_1024.rows) == []


def test_sharded_round_at_1024_peers_reads_no_whole_stack_per_device(
        v5e, sim_1024):
    hlo = _compile_sharded_step(sim_1024, v5e).as_text()
    assert "all-gather" in hlo and "all-reduce" in hlo
    assert "f32[256,480,784]{2,1,0:T(8,128)}" in hlo  # one device's share
    assert whole_stack_instructions(hlo, 256, sim_1024.rows) == []


def _two_step_round(sim):
    """The round's gather as it was before PR 25: the sampled peers' whole
    shards first, then each peer's minibatch rows."""

    one_step = local_step_fn(sim.model, sim.mode, clip=sim.cfg.grad_clip)

    def step(w, stake, it, seed, x, y, x_val, y_val):
        rkey = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(0), seed), it)
        ckey, bkey, _ = jax.random.split(rkey, 3)
        cidx = sim._contributors(ckey)
        bkeys = jax.vmap(lambda i: jax.random.fold_in(bkey, i))(cidx)

        def one(key, xi, yi):
            idx = sample_batch(key, sim.rows, sim.cfg.batch_size)
            return one_step(w, xi[idx], yi[idx])

        return jax.vmap(one)(bkeys, x[cidx], y[cidx])

    return step


@pytest.mark.parametrize("form", ["two_step_default_layout",
                                  "two_step_row_major",
                                  "composed_default_layout"])
def test_either_half_alone_still_passes_over_the_whole_stack(v5e, sim_1024,
                                                             form):
    """So the assertion above cannot pass vacuously: the witness finds the
    parent's whole-stack casts and copies, and finds what is left of them
    when only the index is composed or only the layout is held."""
    step = None if form.startswith("composed") else _two_step_round(sim_1024)
    hlo = _compile_round_step(sim_1024, v5e[0], step=step,
                              stack=form.endswith("row_major")).as_text()
    expect = "{2,1,0" if form.endswith("row_major") else "{0,2,1"
    assert _x_entry_layout(hlo).startswith(expect)
    found = whole_stack_instructions(hlo, 1024, sim_1024.rows)
    assert found, "no whole-stack instruction in a form known to have them"
    assert all("[1024,480," in f or "[491520," in f for f in found)


def test_round_hlo_is_the_program_of_the_stacks_own_format(v5e, sim_1024,
                                                           monkeypatch):
    """`round_hlo()` asks each data argument for its format and compiles
    for it: with the stack where `put_stack` leaves it on the chip, its text
    is the row-major program, the one a trace's names are joined against."""
    one = SingleDeviceSharding(v5e[0])

    class OnChip:  # what round_hlo() asks of an array, on a described chip
        def __init__(self, a, stack=False):
            self.shape, self.dtype = a.shape, a.dtype
            self.format = _abstract([a], one, stack=stack)[0].format

    for name in ("x", "y"):
        monkeypatch.setattr(sim_1024, name,
                            OnChip(getattr(sim_1024, name), stack=True))
    for name in ("x_val", "y_val"):
        monkeypatch.setattr(sim_1024, name, OnChip(getattr(sim_1024, name)))
    hlo = sim_1024.round_hlo()
    assert "tpu_custom_call" in hlo and "round_gather" in hlo
    assert _x_entry_layout(hlo).startswith("{2,1,0:T(8,128)}")
    assert sim_1024.whole_stack_instructions(hlo) == []


# ---------------------- the live path's delta program has the gather (PR 29)


def _compile_hive_deltas(sim, devices, stack=True):
    """runtime/hive.py's delta program for `sim`'s cluster, all of its
    peers co-hosted: on one described chip, or partitioned over several."""
    return _hive_deltas_for(sim.cfg, devices, stack).as_text()


def _hive_deltas_for(cfg, devices, stack=True, block=None):
    """The same for `cfg`'s cluster, compiled; with `block`, its peer axis
    walked that many peers at a time."""
    from biscotti_tpu.runtime.hive import HiveStepper

    hs = HiveStepper(cfg, range(cfg.num_nodes))
    if block:
        hs.steps.block = block  # before the program is traced
    if len(devices) == 1:
        whole = peers = SingleDeviceSharding(devices[0])
    else:
        mesh = jax.sharding.Mesh(np.array(devices), ("peers",))
        whole, peers = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                              P("peers"))
    w = jnp.zeros((hs.num_params,), jnp.float32)
    args = (_abstract([w], whole) + _abstract([hs._batch_keys], peers)
            + _abstract([hs._x, hs._y], peers, stack=stack)
            + _abstract([jnp.asarray(0)], whole)
            + [jax.tree.map(lambda a: _abstract([a], whole)[0],
                            hs._frozen)])
    return hs._deltas.lower(*args).compile()


@pytest.mark.parametrize("stack", ["row_major", "default_layout"])
def test_the_hives_deltas_at_1024_peers_read_no_whole_stack(v5e, sim_1024,
                                                            stack):
    """The stepper places its stack with `put_stack` and takes its rows
    with models/peer_step.py's composed gather: compiled for the chip
    there is no pass over the 1.5 GB stack. Left in the runtime's default
    layout (where `jnp.asarray` put it before) the same program has one,
    which is what the witness is there to find."""
    hlo = _compile_hive_deltas(sim_1024, v5e[:1],
                               stack=stack == "row_major")
    found = whole_stack_instructions(hlo, 1024, sim_1024.rows)
    if stack == "row_major":
        assert _x_entry_layout(hlo).startswith("{2,1,0:T(8,128)}")
        assert found == []
    else:
        assert _x_entry_layout(hlo).startswith("{0,2,1")
        assert found and all("[1024,480," in f or "[491520," in f
                             for f in found)


def test_the_hives_deltas_partition_over_four_chips(v5e, sim_1024):
    """The same program with the stack and the keys sharded: every chip
    steps its 256 peers (the result stays sharded), reads no whole share
    of the stack, and what crosses chips is the minibatch rows' sum."""
    hlo = _compile_hive_deltas(sim_1024, v5e)
    entry = next(l for l in hlo.splitlines()
                 if "entry_computation_layout" in l)
    assert "f32[256,480,784]{2,1,0:T(8,128)}" in entry
    assert "->f32[256,7850]" in entry.replace(" ", "")
    assert whole_stack_instructions(hlo, 256, sim_1024.rows) == []
    assert whole_stack_instructions(hlo, 1024, sim_1024.rows) == []
    assert "f32[1024,10,784]" in hlo and "all-reduce" in hlo


def test_a_walked_block_costs_each_of_four_chips_what_it_costs_one(v5e):
    """Why the stepper sizes a block as ONE device's share
    (`HiveStepper.__init__`): the walk runs over the sharded peer axis, and
    the compiler's partition gathers the minibatches and has every chip
    step every block (the result comes back whole, not a chip's quarter).
    A chip's temporaries are then one chip's at that block, not a quarter
    of them, and sized from the four chips' memory together a block would
    be four times what fits."""
    cfg = _cfg(**{**LM_TINY, "num_nodes": 16})
    on_one = _hive_deltas_for(cfg, v5e[:1], block=2)
    on_four = _hive_deltas_for(cfg, v5e, block=2)
    assert "all-gather" in on_four.as_text()
    one, four = on_one.memory_analysis(), on_four.memory_analysis()
    assert four.output_size_in_bytes == one.output_size_in_bytes  # whole
    assert 0.9 * one.temp_size_in_bytes < four.temp_size_in_bytes \
        < 1.1 * one.temp_size_in_bytes


# ------------------------- the frozen tree and the unchanged classifiers (PR 27)


def _lowered_round(sim):
    """The round's lowered program (StableHLO text) at `sim`'s shapes, as
    `Simulator.round_step` traces it."""
    def round_step(*args):
        return sim._round_step_raw(*args)[:4]

    w, stake = sim.init_state()
    return jax.jit(round_step, donate_argnums=(0, 1)).lower(
        w, stake, 0, jnp.asarray(sim.cfg.seed, jnp.int32), sim.x, sim.y,
        sim.x_val, sim.y_val, sim.frozen).as_text()


# sha256[:16] of the lowered round at these shapes on the commits that had
# it first, read there with this very function: the classifiers' on c71d5aa
# (before `Model` had a frozen tree, a declared step rule or a walked peer
# axis; less the last argument); the two language models' on PR 35's tree,
# for one reason: a block's attention is one `lax.map` over its peers
# (`lm.peer_at_a_time`; they were f9cffd8297eaba60, 25251d8a018f991c and
# 7ae27af2f9aa6149 from PR 32's tree to 6b90dd1), and a layer is a jitted
# function of its kind, traced once a kind and not once a layer. A
# block of ONE peer walks nothing: the published Granite round lowers to
# 6b90dd1's text (de6de7cab9843f86; PERF.md section 5). DeepSeek-V2's tiny
# round is PR 37's (9c84a7f51791d196 until then): its one rotary key is an
# operand of the core, q and the key turn in place (ops/rotary.py)
PARENT_ROUNDS = {
    ("mnist", "softmax"): "2f1f0d7efd64ce2c",
    ("creditcard", ""): "04e1c79a9ba9c99e",
    ("mnist", "mnist_cnn"): "0cb8d0fa17f1cd73",
    ("lm_tokens_tiny", ""): "f0ca8f3e4ab9ba5e",
    ("lm_tokens_tiny", "deepseek_v2_tiny"): "ef6a2362a6303adf",
    # read on fd5ebfc (PR 37) before the conv, the gated norm and the
    # step's law moved to models/lm.py for the second hybrid to share
    ("lm_tokens_tiny", "granite_h_tiny"): "ad3429641a9713fb",
    # PR 49's: a block's delta net runs a peer at a time too, its walk
    # unrolled (d209b29b660e909d from 19984dc, PR 39, until then:
    # ops/attention.py's sink and ops/moe.py's choice bias did not move it)
    ("lm_tokens_tiny", "qwen3_next_tiny"): "fb114805bebced98",
    # read on 63bc454 (PR 44's tree) before the models' builders declared
    # their gauges (PR 46)
    ("lm_tokens_tiny", "mimo_v2_tiny"): "14452e92480a457c",
}
WALKED_IN_THREES = "83f7ac9576367014"  # the same, the peer axis in two blocks

LM_TINY = dict(dataset="lm_tokens_tiny", num_nodes=8, batch_size=2,
               sample_percent=1.0, num_verifiers=1, num_miners=1,
               learning_rate=0.1, grad_clip=1.0)


def _sha(text):
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("dataset,model", sorted(PARENT_ROUNDS))
def test_a_classifiers_lowered_round_is_the_parents(dataset, model):
    """An empty frozen tree adds no argument, `block_step_fn` vmaps the
    same step, the declared rule picks what the model's name picked, and
    the gather and the walk trace the same from models/peer_step.py: the
    program comes out as it was, instruction for instruction. So does the
    language model's, its frozen tree an argument."""
    if dataset == "lm_tokens_tiny":
        sim = Simulator(_cfg(**dict(LM_TINY, model_name=model)))
        assert sim.frozen != {}
    else:
        sim = Simulator(BiscottiConfig(
            dataset=dataset, model_name=model, num_nodes=10, seed=3,
            defense=Defense.KRUM, epsilon=1.0))
        assert sim.frozen == {}
    assert sim.peer_block == sim.cfg.num_samples
    assert _sha(_lowered_round(sim)) == PARENT_ROUNDS[dataset, model]


def test_the_walked_round_is_the_parents():
    """Six peers stepped three at a time (`lax.map` over two blocks of the
    same program), as the parent traced it with `peer_block` 3."""
    sim = Simulator(_cfg(**LM_TINY))
    sim.steps.block = 3
    assert sim.peer_block == 3
    assert _sha(_lowered_round(sim)) == WALKED_IN_THREES


@pytest.fixture(scope="module")
def sim_lm():
    return Simulator(_cfg(**LM_TINY))


def test_the_frozen_tree_is_a_parameter_and_no_constant(sim_lm):
    """Closed over, the base would be constants of the program (6 GB at
    the published size): every frozen leaf is an entry parameter of the
    compiled round, and no constant has a leaf's element count."""
    import math
    import re

    hlo = sim_lm.round_hlo()
    entry = hlo[hlo.index("ENTRY "):]
    params = re.findall(r"= (\w+)\[([\d,]*)\]\S* parameter\(", entry)
    shapes = [tuple(int(v) for v in dims.split(",") if v)
              for _, dims in params]
    leaves = jax.tree.leaves(sim_lm.frozen)
    assert len(leaves) > 50
    for leaf in leaves:
        assert tuple(leaf.shape) in shapes, leaf.shape
    assert len(params) == 8 + len(leaves)
    # (the causal masks [16, 16] and the rotary tables ARE constants; a
    # weight matrix of the tiny model has 1,024 elements or more)
    big = 1024
    assert sum(leaf.size >= big for leaf in leaves) > 10
    for dims in re.findall(r"= \w+\[([\d,]*)\]\S* constant\(", hlo):
        assert math.prod(int(v) for v in dims.split(",") if v) < big, dims
    # and the round's walked blocks are one loop over the same program
    assert sim_lm.peer_block == sim_lm.cfg.num_samples == 6


def test_the_language_model_round_compiles_for_v5e(v5e, sim_lm):
    """The tiny model's whole round for the chip: the grouped products
    lower (a compiler-made kernel), under x64."""
    one = SingleDeviceSharding(v5e[0])
    w, stake = sim_lm.init_state()
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim_lm.cfg.seed, jnp.int32)], one)
            + _abstract([sim_lm.x, sim_lm.y], one, stack=True)
            + _abstract([sim_lm.x_val, sim_lm.y_val], one)
            + [jax.tree.map(lambda a: _abstract([a], one)[0],
                            sim_lm.frozen)])
    hlo = jax.jit(sim_lm._round_step_raw).lower(*args).compile().as_text()
    assert "ragged-dot" in hlo
    wide = [line.strip()[:160] for line in hlo.splitlines()
            if "f64[" in line or ("s64[" in line and "parameter(" not in line
                                  and "lm_" in line)]
    assert not wide, wide[:5]


# (experts a token, held, of all, hidden size, expert width) as published
EXPERTS = {"deepseek_v2": (6, 40, 160, 5120, 1536),
           "laguna": (10, 64, 256, 3072, 1024),
           "qwen3_next": (10, 128, 512, 2048, 512)}


def _experts_gradient(device, model, remat=False):
    """`held_experts`' gradient in the rows and their coefficients as a
    peer block of 3 sends it (3,072 tokens, `model`'s published expert
    shapes, bfloat16), compiled for the chip; with `remat`, inside a layer
    that adds the result to its input, rematerialised as models/lm.py's
    decoder has it."""
    from biscotti_tpu.ops import moe

    one = SingleDeviceSharding(device)
    k, e, total, h, f = EXPERTS[model]
    n = 3072

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    weights = {"w_gate": shape((e, h, f), jnp.bfloat16),
               "w_up": shape((e, h, f), jnp.bfloat16),
               "w_down": shape((e, f, h), jnp.bfloat16)}

    def layer(x, coef, experts, weights):
        out, counts = moe.held_experts(x, experts, coef, weights, 0, total)
        return (x + out if remat else out), counts

    def loss(*args):
        out, counts = (jax.checkpoint(layer) if remat else layer)(*args)
        return jnp.sum(out * out), counts

    return jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True)).lower(
        shape((n, h), jnp.float32), shape((n, k), jnp.float32),
        shape((n, k), jnp.int32), weights).compile()


def test_the_expert_layer_at_the_published_shapes_takes_the_kernel(v5e):
    """`held_experts` under `jax.grad` as a peer block of 3 sends it
    (3,072 tokens, ten a token, 64 of 256 experts held, bfloat16) compiles
    for the v5e under x64, and every grouped product of it, on the cut
    buffer and on the uncut one, forward and backward, is
    ops/grouped_matmul.py's kernel: no `ragged-dot` is left."""
    hlo = _experts_gradient(v5e[0], "laguna").as_text()
    assert "ragged-dot" not in hlo
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert "conditional(" in hlo
    for rows in (15360, 30720):  # the two sides of the `lax.cond`
        of_rows = [line for line in calls if f"[{rows}," in line]
        # forward: two [rows, 1024] and one [rows, 3072], float32; backward
        # (the weights read transposed): the same in bfloat16
        assert len(of_rows) >= 6, (rows, len(of_rows))
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]
    # no array of the expert stack's shape is made: no weight's gradient
    stack = re.compile(r" = (bf16|f32)\[64,(3072,1024|1024,3072)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            if stack.search(line) and "parameter(" not in line
            and "get-tuple-element" not in line]
    assert not made, made[:5]


def _described_layer(v5e, build, cfg, at, length=1024, peers=1):
    """(sharding, model, frozen leaves, adapters with a peer axis of
    `peers`) of layer `at` of a language model `build(name, cfg, length)`,
    as shapes on the described chip."""
    one = SingleDeviceSharding(v5e[0])
    model = build("lm", cfg, length)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    frozen = on_chip(jax.eval_shape(
        model.init_frozen, jax.random.PRNGKey(0))["layers"][at])
    adapters = on_chip(jax.eval_shape(
        lambda key: jax.tree.map(lambda b: jnp.stack([b] * peers),
                                 model.init(key)["layers"][at]),
        jax.random.PRNGKey(0)))
    return one, model, frozen, adapters


def _block_gradient(block, described, argnums=(0, 1)):
    """`block(h, frozen, adapters)` of a `_described_layer` under
    `jax.checkpoint` and `jax.grad` (in the adapters and the input, or as
    `argnums` says), compiled for the described chip under x64; a block
    that gives a layer's triple is read by its hidden states."""
    one, model, frozen, adapters = described
    peers = jax.tree.leaves(adapters)[0].shape[0]

    def loss(adapters, h, frozen):
        out = jax.checkpoint(block)(h, frozen, adapters)
        out = out[0] if isinstance(out, tuple) else out
        return jnp.sum(out * out)

    h = jax.ShapeDtypeStruct(
        (peers, 1, model.d_in, model.info["config"].hidden), jnp.float32,
        sharding=one)
    return jax.jit(jax.grad(loss, argnums=argnums)).lower(
        adapters, h, frozen).compile()


@pytest.mark.parametrize("at,heads,kind", [(0, 48, "full"),
                                           (1, 72, "sliding")])
def test_the_attention_at_the_published_shapes_takes_the_kernel(v5e, at,
                                                                heads, kind):
    """`_attention` as a peer block of 3 sends it (3 windows of 1,024, the
    published widths, bfloat16) under `jax.checkpoint` and `jax.grad`
    compiles for the v5e under x64 with ops/attention.py's kernel as its
    core, forward, recomputation and backward, and makes NO float32 array of
    the scores' size."""
    from biscotti_tpu.models import laguna

    cfg = laguna.PRESETS["laguna_s_fedlora"]
    assert (cfg.heads[at], cfg.layer_types[at]) == (heads, kind)
    hlo = _block_gradient(
        lambda h, f, a: laguna._attention(cfg, at, h, f, a),
        _described_layer(v5e, laguna.laguna_model, cfg, at,
                         peers=3)).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    # forward (the primal's is dead code under the gradient: the
    # recomputation's is what is left) and the fused backward
    assert 2 <= len(calls) <= 3, len(calls)
    assert any(f"f32[3,8,{heads // 8},1024,128]" in c for c in calls)
    assert any(f"bf16[3,8,{heads // 8},1024,128]" in c for c in calls)
    # (k's and v's projections are f32[3, 1024, 8 * 128]: a head's scores
    # of one window are as many elements again, and no array ends in
    # [1024, 1024] and has more)
    square = re.compile(r"f32\[([\d,]*1024,1024)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 3 * 1024 * 1024]
    assert not made, made[:5]
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]


def test_the_latent_attention_at_the_published_shapes_takes_the_kernel(v5e):
    """DeepSeek-V2's `_attention` as a peer block of 3 sends it (3 windows
    of 1,024, 128 heads, scores over 192 and values of 128, bfloat16) under
    `jax.checkpoint` and `jax.grad` compiles for the v5e under x64 with
    ops/attention.py's kernel as its core, the 192 as they are, and makes
    NO float32 array of the scores' size. Since PR 37 the one rotary key
    reaches the core as an operand of its own, `bf16[3, 1, 1024, 64]`: no
    array holds a head's 192-wide key, none is a broadcast of the one key
    to 128 heads, and q's 64 turn in ops/rotary.py's one pass."""
    from biscotti_tpu.models import deepseek_v2

    cfg = deepseek_v2.PRESETS["deepseek_v2_fedlora"]
    described = _described_layer(v5e, deepseek_v2.deepseek_v2_model, cfg, 1,
                                 peers=3)
    assert described[1].info["attention"] == {
        "fused": 1, "block_share": 0.75, "shared_key": 1}
    hlo = _block_gradient(
        lambda h, f, a: deepseek_v2._attention(cfg, h, f, a),
        described).as_text()
    kernels = [line for line in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    calls = [c for c in kernels if "attention_" in c.split(" = ")[0]]
    assert 2 <= len(calls) <= 3, len(calls)
    assert any("f32[3,128,1,1024,128]" in c for c in calls)    # the result
    assert any("bf16[3,128,1,1024,192]" in c for c in calls)   # q, dq
    assert all("mla_core" in c for c in calls)
    # every call takes the one key, 64 wide, and k 128 wide; the backward
    # hands the one key's cotangent out, summed over the heads inside
    for c in calls:
        operands = c[c.index("operand_layout_constraints="):]
        assert "bf16[3,1,1024,64]" in operands, c[:200]
        assert "bf16[3,128,1024,192]" not in operands, c[:200]
    assert any("bf16[3,1,1024,64]" in c.split(" custom-call(")[0]
               for c in calls)
    # q's turn, token-major float32 in and head-major bfloat16 out: forward
    # and recomputed; and the cotangent's turn back, the other way
    turns = [c.split(" custom-call(")[0].strip() for c in kernels
             if "attn_rotary" in c]
    assert len(turns) == 3, turns
    assert sum(c.startswith("%rotary_to_heads")
               and "bf16[3,128,1024,192]" in c for c in turns) == 2
    assert sum(c.startswith("%rotary_from_heads")
               and "f32[3,1024,24576]" in c for c in turns) == 1
    # no float32 array of a head-major or token-major [128 heads, 1,024,
    # 192] comes out of a concatenation, a pad or a broadcast (the parent
    # made q and k so, and their cotangents by pads), and the one key is
    # never broadcast to the heads
    wide = re.compile(r" = f32\[(\d+,)?(128,1024|1024,128),192\]\S* "
                      r"(concatenate|pad|broadcast)\(")
    keys = re.compile(r" = \w+\[(\d+,)?(128,1024|1024,128),64\]\S* "
                      r"broadcast\(")
    made = [line.strip()[:160] for line in hlo.splitlines()
            if wide.search(line) or keys.search(line)]
    assert not made, made[:5]
    square = re.compile(r"f32\[([\d,]*1024,1024)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 3 * 1024 * 1024]
    assert not made, made[:5]
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]


# sha256[:16] of the lowered gradient of ONE `attention.attention` call at
# the sibling models' published shapes (a window, bfloat16), read on
# 7bdda71 with `_lowered_core` before ops/attention.py learnt to take a
# shared key part: a call without one traces and lowers as it did (on the
# CPU the kernel lowers interpreted: its every operation, grid and block
# index is in the text)
PARENT_CORES = {
    "laguna_full": ((8, 6, 128, 1024, None), "9d053ecc4199a93b"),
    "laguna_sliding": ((8, 9, 128, 512, None), "74f9a694f33eac20"),
    "granite": ((8, 4, 64, 1024, 0.015625), "aab27e2608273730"),
    # read on 19984dc (PR 39) before the kernel learnt a sink: Qwen3-Next's
    # gated attention, eight query heads on a key/value head of 256 | 256
    "qwen3_next": ((2, 8, 256, 1024, None), "cf976d71992edb8b"),
}
# the same of DeepSeek-V2's call WITH its shared key part (G = 1, k of 128
# beside the one rotary key of 64, the scores times 0.1), read on 19984dc
PARENT_SHARED_CORE = "7c7bddcd95a81ab8"


def _lowered_core(kv, g, d, window, scale, t=1024):
    from biscotti_tpu.ops import attention

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16)

    def loss(q, k, v):
        out = attention.attention(q, k, v, window, scale)
        return jnp.sum(out * out)

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        shape(1, kv, g, t, d), shape(1, kv, t, d), shape(1, kv, t, d)
    ).as_text()


@pytest.mark.parametrize("layer", sorted(PARENT_CORES))
def test_a_core_without_a_shared_key_lowers_as_the_parents(layer):
    """Laguna's two attention calls (G = 6 under the causal mask, G = 9
    under a window of 512; heads of 128 | 128) and Granite's (G = 4, 64 |
    64, the scores times 1 / 64) pass no shared key part: the operand is
    not there, and the program is the parent's."""
    from biscotti_tpu.models import granite_hybrid, laguna

    big = laguna.PRESETS["laguna_s_fedlora"]
    assert (big.kv_heads, big.head_dim, big.window) == (8, 128, 512)
    assert {n // big.kv_heads for n in big.heads} == {6, 9}
    hybrid = granite_hybrid.PRESETS["granite_h_micro_fedlora"]
    assert (hybrid.kv_heads, hybrid.heads // hybrid.kv_heads,
            hybrid.head_dim, hybrid.attention_multiplier) == (8, 4, 64,
                                                              0.015625)
    shape, parent = PARENT_CORES[layer]
    assert _sha(_lowered_core(*shape)) == parent


def test_a_core_with_a_shared_key_and_no_sink_lowers_as_the_parents():
    """DeepSeek-V2's call passes a shared key part and no sink: the sink's
    operand is not there, and the program is the parent's."""
    from biscotti_tpu.ops import attention

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16)

    def loss(q, k, v, r):
        out = attention.attention(q, k, v, 1024, 0.1, shared=r)
        return jnp.sum(out * out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        shape(1, 16, 1, 1024, 192), shape(1, 16, 1024, 128),
        shape(1, 16, 1024, 128), shape(1, 1, 1024, 64)).as_text()
    assert _sha(text) == PARENT_SHARED_CORE


def test_experts_of_5120_by_1536_take_the_kernel_in_column_tiles(v5e):
    """`held_experts` at DeepSeek-V2's published shapes (a peer block of 3:
    3,072 tokens, six a token, 40 of 160 experts held, H = 5,120, F =
    1,536, bfloat16): one expert's weight is 15.7 MB, past what the
    kernel's buffers hold whole, so `column_tile` cuts it (256 columns of
    the 1,536, 1,024 of the 5,120) and every grouped product is still
    ops/grouped_matmul.py's."""
    from biscotti_tpu.ops import grouped_matmul

    n, (k, e, total, h, f) = 3072, EXPERTS["deepseek_v2"]
    tile = grouped_matmul.row_tile(n * k / total)
    assert tile == 128
    for rows in (n * k // 2, n * k):  # the cut buffer and the uncut one
        assert grouped_matmul.column_tile(rows, h, f, jnp.bfloat16,
                                          tile) == 256
        assert grouped_matmul.column_tile(rows, f, h, jnp.bfloat16,
                                          tile) == 1024
    hlo = _experts_gradient(v5e[0], "deepseek_v2").as_text()
    assert "ragged-dot" not in hlo
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) >= 12, len(calls)  # six a side of the `lax.cond`
    stack = re.compile(r" = (bf16|f32)\[40,(5120,1536|1536,5120)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            if stack.search(line) and "parameter(" not in line
            and "get-tuple-element" not in line]
    assert not made, made[:5]


# temporaries of one sparse layer's rematerialised gradient, bytes: read
# here at 1,238,101,504 (DeepSeek-V2's shapes) and 1,237,422,592 (Laguna's);
# with the `lax.cond` that `jax.grad` split (ffcbf59, by this very program)
# they read as below: its backward's branches handed the stacks out
EXPERTS_TEMPORARIES = 1_500_000_000
SPLIT_COND_TEMPORARIES = {"deepseek_v2": 2_555_475_968,
                          "laguna": 2_650_463_232}


@pytest.mark.parametrize("model", sorted(SPLIT_COND_TEMPORARIES))
def test_a_sparse_layers_gradient_holds_no_copy_of_an_expert_stack(v5e,
                                                                   model):
    """The choice between the cut and the uncut sorted buffer is made where
    no residual crosses it (ops/moe.py: `_routed`): compiled for the v5e,
    a rematerialised sparse layer's gradient at the published expert shapes
    makes no array of a stack's shape, copies none, hands none out of a
    `conditional`, and its temporaries stay under a bound that the split
    `lax.cond` passed by more than a stack (629 MB and 403 MB)."""
    _, e, _, h, f = EXPERTS[model]
    compiled = _experts_gradient(v5e[0], model, remat=True)
    hlo = compiled.as_text()
    stack = rf"bf16\[{e},({h},{f}|{f},{h})\]"
    lines = [line.strip() for line in hlo.splitlines()]
    made = [line[:160] for line in lines
            if re.search(" = " + stack, line) and "parameter(" not in line
            and "get-tuple-element(" not in line]
    assert not made, made[:5]
    handed = [line[:160] for line in lines if " conditional(" in line
              and re.search(stack, line.split(" conditional(")[0])]
    assert not handed, handed[:5]
    assert len([line for line in lines if " conditional(" in line]) == 2
    calls = [line for line in lines
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 18  # 9 a side: primal 3, recomputed 3, transposed 3
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    assert temporaries < EXPERTS_TEMPORARIES, temporaries
    assert EXPERTS_TEMPORARIES + 2 * e * h * f < SPLIT_COND_TEMPORARIES[model]


# ------------------- what a published round lowers to, by hash (PR 48)
# (appended: the tests above run on the schedule they had)

_MOSAIC_BODY = re.compile(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22')


def _lowered_sha(lowered):
    """sha256[:16] of a lowered program's text less its Mosaic bodies: a
    kernel's payload holds its call stack, file by file and line by line,
    so it differs from one checkout's path to the next; everything else of
    the text is the program's alone."""
    return _sha(_MOSAIC_BODY.sub("", lowered.as_text()))


# read on 27a3ed4 (PR 46's tree) before ops/delta_rule.py learnt heads that
# are no whole lane tiles: the published rounds that share the rule's
# module or the hybrids' mixer parts (tests/test_v4_qwen3_next_lowering.py,
# tests/test_v3_granite_lowering.py hold their whole rounds to these).
# Qwen3-Next's is PR 49's, whose block of 3 runs its delta net a peer at a
# time (ed891139b7db5505 until then); Granite's, a block of 1, is as read
PARENT_PUBLISHED_ROUNDS = {"lm_tokens_qwen3next": "10d4c12b795b4e77",
                           "lm_tokens_granite": "62d702f5222ff058"}
# and the rule's kernel pair itself at Qwen3-Next's shapes, as a jaxpr
# (the text of both kernels' bodies, no path in it)
PARENT_RULE_KERNELS = "9936be577aabdcf3"


def test_the_rules_kernels_at_whole_tiles_trace_as_the_parents():
    """16 key heads of 128 serving 32 value heads of 128, one window of
    1,024 tokens, bfloat16, forward and backward: the jaxpr of `rule`
    under `jax.vjp` (both `pallas_call`s with their bodies, two key heads
    and four value heads a step) is the parent's, statement for statement:
    `heads_a_step`, `laid` and `layout` changed nothing of what Qwen3-Next
    runs."""
    from biscotti_tpu.ops import delta_rule

    shape = jax.ShapeDtypeStruct
    args = (shape((1, 1024, 16, 128), jnp.bfloat16),
            shape((1, 1024, 16, 128), jnp.bfloat16),
            shape((1, 1024, 32, 128), jnp.bfloat16),
            shape((1, 1024, 32), jnp.float32),
            shape((1, 1024, 32), jnp.float32),
            shape((1, 1024, 32, 128), jnp.float32))

    def both(q, k, v, g, beta, cot):
        out, back = jax.vjp(lambda *a: delta_rule.rule(*a, 64), q, k, v, g,
                            beta)
        return out, back(cot)

    assert delta_rule.heads_a_step(16, 32) == 2
    assert _sha(str(jax.make_jaxpr(both)(*args))) == PARENT_RULE_KERNELS
