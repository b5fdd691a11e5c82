"""The WHOLE published round of MiMo-V2.5's share compiled ahead of time
for a described v5e (tests/test_tpu_lowering.py has its two kinds of
attention block at the published shapes; this is a minute on every core,
in a file of its own that is collected LAST, as
tests/test_v3_granite_lowering.py is and for its reason)."""

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from test_tpu_lowering import _abstract, _cfg, v5e  # noqa: F401  (fixture)

SHARE = dict(dataset="lm_tokens_mimo", num_nodes=30, batch_size=1,
             sample_percent=0.7, num_verifiers=3, num_miners=3,
             num_noisers=2, learning_rate=0.1, grad_clip=1.0)


def test_the_published_window_attention_round_compiles_for_v5e(v5e,
                                                               monkeypatch):
    """The WHOLE round of `mimo_v2_fedlora.device_round` (30 peers, 21
    sampled, one window of 2,048 tokens each, DP noise, Krum, the held-out
    windows' forward; 7 layers, three kinds traced once each, each
    rematerialised) compiles for a described v5e with the base NEVER drawn
    (zeros in its place: the compile sees shapes), walks its peers one at
    a time and fits: 11.69 GB of base and the stacks as arguments, 2.22 GB
    of temporaries; every grouped product and attention core a kernel."""
    from biscotti_tpu.models import lm

    monkeypatch.setattr(lm, "_draw", lambda key, shape, fan_in, dtype:
                        jnp.zeros(shape, dtype))
    sim = Simulator(_cfg(**SHARE))
    assert sim.num_params == 2080768 and sim.cfg.num_samples == 21
    assert sim.frozen_bytes() == 2 * 5847250752
    assert sim.peer_block == 1
    assert sim.x.shape == (30, 64, 2048)
    one = SingleDeviceSharding(v5e[0])
    w, stake = sim.init_state()
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim.cfg.seed, jnp.int32)], one)
            + _abstract([sim.x, sim.y], one, stack=True)
            + _abstract([sim.x_val, sim.y_val], one)
            + [jax.tree.map(lambda a: _abstract([a], one)[0], sim.frozen)])
    compiled = jax.jit(sim._round_step_raw).lower(*args).compile()
    memory = compiled.memory_analysis()
    assert 11.7e9 < memory.argument_size_in_bytes < 11.8e9
    assert memory.temp_size_in_bytes < 2.4e9
    assert memory.generated_code_size_in_bytes < 0.3e9  # no stack copied
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "ragged-dot" not in hlo
    for scope in ("lm_attention", "attn_core_swa", "attn_core_full",
                  "attn_in", "attn_rotary", "lm_router", "lm_experts",
                  "lm_dense", "lm_head_loss"):
        assert scope in hlo, scope
    assert "peer_walk" not in hlo  # a block of one peer walks nothing
    assert "attention_forward" in hlo and "attention_backward" in hlo
