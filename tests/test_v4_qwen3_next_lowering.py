"""The WHOLE published round of Qwen3-Next-80B-A3B's share compiled ahead
of time for a described v5e (tests/test_tpu_lowering.py has its two kinds
of mixer and its experts at the published shapes; this is three minutes on
every core, in a file of its own that is collected LAST, as
tests/test_v3_granite_lowering.py is and for its reason)."""

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from test_tpu_lowering import _abstract, _cfg, v5e  # noqa: F401  (fixture)

HYBRID = dict(dataset="lm_tokens_qwen3next", num_nodes=30, batch_size=1,
              sample_percent=0.7, num_verifiers=3, num_miners=3,
              num_noisers=2, learning_rate=0.1, grad_clip=1.0)


def test_the_published_delta_net_round_compiles_for_v5e(v5e, monkeypatch):
    """The WHOLE round of `qwen3_next_fedlora.device_round` (30 peers, 21
    sampled, one window each, DP noise, Krum, the held-out windows'
    forward; 12 layers, two kinds traced once each, each rematerialised)
    compiles for a described v5e with the base NEVER drawn (zeros in its
    place: the compile sees shapes), walks its peers three at a time
    (one while the delta rule was `jax.numpy`: PR 39's recount of
    `step_bytes`) and fits: 10.85 GB of base and the stacks as arguments,
    3.98 GB of temporaries; every grouped product, attention core and
    delta rule a kernel, and no `triangular_solve`."""
    from biscotti_tpu.models import lm

    monkeypatch.setattr(lm, "_draw", lambda key, shape, fan_in, dtype:
                        jnp.zeros(shape, dtype))
    sim = Simulator(_cfg(**HYBRID))
    assert sim.num_params == 2605056 and sim.cfg.num_samples == 21
    assert sim.frozen_bytes() == 2 * 5424460992
    assert sim.peer_block == 3
    assert sim.model.info["gdn_rule"]["kernel"] == 1
    one = SingleDeviceSharding(v5e[0])
    w, stake = sim.init_state()
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim.cfg.seed, jnp.int32)], one)
            + _abstract([sim.x, sim.y], one, stack=True)
            + _abstract([sim.x_val, sim.y_val], one)
            + [jax.tree.map(lambda a: _abstract([a], one)[0], sim.frozen)])
    compiled = jax.jit(sim._round_step_raw).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert 10.8e9 < memory.argument_size_in_bytes < 10.9e9
    assert memory.temp_size_in_bytes < 4.2e9
    assert memory.generated_code_size_in_bytes < 0.3e9  # no stack copied
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "ragged-dot" not in hlo and "triangular" not in hlo
    for scope in ("gdn_rule", "gdn_proj", "gdn_conv", "gdn_gate",
                  "lm_attention", "attn_core", "lm_router", "lm_experts",
                  "lm_dense", "lm_head_loss"):
        assert scope in hlo, scope
    assert "peer_walk" in hlo  # a block's attention, a peer at a time
    assert "delta_rule_forward" in hlo and "delta_rule_backward" in hlo
