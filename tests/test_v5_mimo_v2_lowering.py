"""MiMo-V2.5's share compiled ahead of time for a described v5e: the WHOLE
published round (a minute on every core), then its two kinds of attention
block at the published shapes (in tests/test_tpu_lowering.py until PR 46;
tests/test_v3_granite_lowering.py says why they are here)."""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from test_tpu_lowering import (  # noqa: F401  (v5e: the fixture)
    _abstract, _block_gradient, _cfg, _described_layer, v5e)

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


# ---- a learned sink, 192 | 128 under grouped queries, 2,048 tokens (PR 40)


@pytest.mark.parametrize("at,kind,kv", [(1, "window", 8), (5, "full", 4)])
def test_the_sink_and_the_wide_groups_at_2048_tokens_take_the_kernel(
        v5e, at, kind, kv):
    """An attention block of the published MiMo-V2.5 share as a peer sends
    it (1 window of 2,048 tokens, 64 query heads of 192 | 128 on 8 (window
    of 128, a learned sink a head) or 4 (causal) key/value heads, bfloat16)
    under `jax.checkpoint` and `jax.grad` compiles for the v5e under x64
    with ops/attention.py's kernel as its core: a key/value head's 8 or 16
    query heads do not fit the kernel's buffers beside 2,048 keys, so they
    go a head at a time at blocks of 256 x 256 (`group_split`: of the
    sub-groups that fit, the one whose block is fastest), each with its own
    copy of its key/value head, the sinks reach the forward
    kernel through SMEM, and NO float32 array of the scores' size is made
    (all 64 heads' would be 1.07 GB). The full kind's block is
    differentiated in its adapters alone: ALONE, with its input's cotangent
    asked for too, the compiler fuses the transpose of the 13,568-column
    product with the norm's backward into one fusion that wants 19.7 MB of
    its 16 MB of scoped VMEM and gives up ("please file a bug against
    XLA"); inside the whole round it fuses otherwise and compiles
    (tests/test_v5_mimo_v2_lowering.py; PERF.md section 7)."""
    from biscotti_tpu.models import mimo_v2
    from biscotti_tpu.ops import attention

    cfg = mimo_v2.PRESETS["mimo_v2_fedlora"]
    assert cfg.kind(at)[0] == kind and cfg.kv_heads[cfg.pattern[at]] == kv
    g = cfg.heads // kv
    assert attention.blocks(g, 2048, 192, jnp.bfloat16, 128) is None
    assert attention.group_split(g, 2048, 192, jnp.bfloat16, 128) == g
    assert attention.blocks(1, 2048, 192, jnp.bfloat16, 128) == (256, 256)
    described = _described_layer(v5e, mimo_v2.mimo_v2_model, cfg, at,
                                 length=2048)
    assert ("sink" in described[2]) == (kind == "window")
    compiled = _block_gradient(
        lambda h, f, a: mimo_v2._attention(cfg, kind, h, f, a), described,
        argnums=(0, 1) if kind == "window" else (0,))
    hlo = compiled.as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert 2 <= len(calls) <= 3, len(calls)
    assert any("f32[1,64,1,2048,128]" in c for c in calls)    # the result
    assert any("bf16[1,64,1,2048,192]" in c for c in calls)   # q, dq
    scope = "attn_core_swa" if kind == "window" else "attn_core_full"
    assert all(scope in c for c in calls)
    square = re.compile(r"f32\[([\d,]*2048,2048)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 2048 * 2048]
    assert not made, made[:5]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]
