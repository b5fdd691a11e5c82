"""Ahead-of-time compilation for the TPU v5e, from the CPU, WITH x64 on.

The cheap pre-flight before spending chip time: libtpu can describe a
`v5e:2x2` topology without a chip, and `jit(...).lower(...).compile()`
against its devices runs the real TPU compiler (Mosaic included). Every
live entry point enables `jax_enable_x64`, and the rest of tier-1 can only
run Pallas kernels in interpret mode, where int64 is legal — so a kernel
that cannot lower for the chip under x64 is invisible to it. Here it fails.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from biscotti_tpu.config import BiscottiConfig, Defense
from biscotti_tpu.ops.krum_pallas import krum_scores_pallas
from biscotti_tpu.parallel.sim import Simulator, sharded_round_step_fn


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


def _abstract(arrays, sharding):
    return [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in arrays]


def _compile_round_step(sim, device):
    w, stake = sim.init_state()
    args = _abstract(
        [w, stake, jnp.asarray(0), jnp.asarray(sim.cfg.seed, jnp.int32),
         sim.x, sim.y, sim.x_val, sim.y_val], SingleDeviceSharding(device))
    return jax.jit(sim._round_step_raw).lower(*args).compile()


def _compile_sharded_step(sim, devices):
    mesh = jax.sharding.Mesh(np.array(devices), ("peers",))
    rep, peers = NamedSharding(mesh, P()), NamedSharding(mesh, P("peers"))
    w = jnp.zeros((sim.num_params,), jnp.float32)
    args = (_abstract([w], rep) + _abstract([sim.x, sim.y], peers)
            + _abstract([jnp.asarray(0),
                         jnp.asarray(sim.cfg.seed, jnp.int32)], rep))
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
