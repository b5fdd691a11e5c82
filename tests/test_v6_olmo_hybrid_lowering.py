"""Olmo-Hybrid-7B's first stage compiled ahead of time for a described
v5e: the WHOLE published round, then its two kinds of mixer at the
published shapes, the rule on the kernel at heads of 96 | 192
(tests/test_v3_granite_lowering.py says why a model's compiles stand in a
file of their own)."""

import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from test_tpu_lowering import (  # noqa: F401  (v5e: the fixture)
    _abstract, _block_gradient, _cfg, _described_layer, v5e)

HYBRID = dict(dataset="lm_tokens_olmo", num_nodes=30, batch_size=1,
              sample_percent=0.7, num_verifiers=3, num_miners=3,
              num_noisers=2, learning_rate=0.1, grad_clip=1.0)


def test_the_published_olmo_round_compiles_for_v5e(v5e, monkeypatch):
    """The WHOLE round of `olmo_hybrid_fedlora.device_round` (30 peers, 21
    sampled, one window each, DP noise, Krum, the held-out windows'
    forward; 16 layers, two kinds traced once each, each rematerialised)
    compiles for a described v5e with the base NEVER drawn (zeros in its
    place: the compile sees shapes), with the rule on the kernel
    (`plan()["kernel"] == 1`: five value heads a step, heads of 96 | 192
    laid in 128 | 256), walks its peers one at a time and fits: 8.21 GB of
    base and the stacks as arguments, 3.83 GB of temporaries; every
    attention core and delta rule a kernel, and no `triangular_solve`."""
    from biscotti_tpu.models import lm

    monkeypatch.setattr(lm, "_draw", lambda key, shape, fan_in, dtype:
                        jnp.zeros(shape, dtype))
    sim = Simulator(_cfg(**HYBRID))
    assert sim.num_params == 5038080 and sim.cfg.num_samples == 21
    assert sim.frozen_bytes() == 2 * 4103615184
    assert sim.peer_block == 1
    assert sim.model.info["gdn_rule"] == {
        "kernel": 1, "states_saved": 1, "key_heads_a_step": 5,
        "value_heads_a_step": 5, "padded_share": 0.4375}
    one = SingleDeviceSharding(v5e[0])
    w, stake = sim.init_state()
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim.cfg.seed, jnp.int32)], one)
            + _abstract([sim.x, sim.y], one, stack=True)
            + _abstract([sim.x_val, sim.y_val], one)
            + [jax.tree.map(lambda a: _abstract([a], one)[0], sim.frozen)])
    compiled = jax.jit(sim._round_step_raw).lower(*args).compile()
    memory = compiled.memory_analysis()
    print(f"arguments {memory.argument_size_in_bytes} bytes, temporaries "
          f"{memory.temp_size_in_bytes} bytes")
    assert 8.2e9 < memory.argument_size_in_bytes < 8.3e9
    assert 3.5e9 < memory.temp_size_in_bytes < 4.0e9
    assert memory.generated_code_size_in_bytes < 0.3e9  # no stack copied
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo
    assert "triangular" not in hlo
    for scope in ("gdn_rule", "gdn_proj", "gdn_conv", "gdn_gate",
                  "lm_attention", "attn_core", "attn_norms", "lm_dense",
                  "lm_head_loss"):
        assert scope in hlo, scope
    assert "peer_walk" not in hlo  # a block of one peer walks nothing
    assert "delta_rule_forward" in hlo and "delta_rule_backward" in hlo


def test_the_delta_rule_at_heads_of_96_by_192_is_the_kernel(v5e):
    """One gated delta-net mixer of the published size as a peer sends it
    (1 window of 1,024 tokens: 16 chunks of 64, 30 key heads of 96 serving
    30 value heads of 192, bfloat16, beta in (0, 2)) under `jax.checkpoint`
    and `jax.grad` compiles for the v5e under x64, and under scope
    `gdn_rule` there are ops/delta_rule.py's three `tpu_custom_call`s, each
    booked under `gdn_rule`, on heads laid in whole lane tiles: q as bf16
    [1, 1024, 30 x 128], the chunks' entry states float32 [1, 16, 30, 128,
    256]; nothing of a chunk's system is an array, there is no `while` and
    nothing is 64 bits wide."""
    from biscotti_tpu.models import olmo_hybrid

    cfg = olmo_hybrid.PRESETS["olmo_hybrid_fedlora"]
    described = _described_layer(v5e, olmo_hybrid.olmo_hybrid_model, cfg, 0)
    assert described[1].info["gdn_rule"]["kernel"] == 1
    compiled = _block_gradient(
        lambda h, f, a: olmo_hybrid._delta_net(cfg, h, f, a), described)
    hlo = compiled.as_text()
    for scope in ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_gate"):
        assert scope in hlo, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3, len(calls)
    scopes = re.compile("|".join(olmo_hybrid.SCOPES))
    for call in calls:
        name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert scopes.findall(name)[-1] == "gdn_rule", name
    assert all("f32[1,16,30,128,256]" in c for c in calls)  # entry states
    assert any("bf16[1,1024,3840]" in c for c in calls)     # q, laid
    assert any("bf16[1,1024,7680]" in c for c in calls)     # v, laid
    chunk = re.compile(r"f32\[[\d,]*,64,(?:64|384)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            if chunk.search(line.split(" = ")[-1].split("(")[0])]
    assert not made, made[:5]
    assert " while(" not in hlo and "triangular" not in hlo
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]


def test_the_full_attention_at_30_heads_of_128_takes_the_kernel(v5e):
    """A full layer of the published size (30 query heads on 30 key/value
    heads of 128 | 128, groups of ONE, no rotary) under `jax.checkpoint`
    and `jax.grad`: `blocks` took (256, 512), ops/attention.py UNEDITED,
    so the core is its kernel and no float32 array of the scores' size
    [30, 1024, 1024] is made."""
    from biscotti_tpu.models import olmo_hybrid
    from biscotti_tpu.ops import attention

    cfg = olmo_hybrid.PRESETS["olmo_hybrid_fedlora"]
    assert attention.blocks(1, 1024, 128, jnp.bfloat16) == (256, 512)
    assert olmo_hybrid.attention_plan(cfg, 1024) == {"fused": 1,
                                                     "block_share": 0.75}
    hlo = _block_gradient(
        lambda h, f, a: olmo_hybrid._attention(cfg, h, f, a),
        _described_layer(v5e, olmo_hybrid.olmo_hybrid_model, cfg,
                         3)).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert 2 <= len(calls) <= 3, len(calls)
    assert any("bf16[1,30,1,1024,128]" in c for c in calls)   # q, dq
    square = re.compile(r"f32\[([\d,]*1024,1024)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 1024 * 1024]
    assert not made, made[:5]
    assert "attn_rotary" not in hlo
