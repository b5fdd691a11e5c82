"""Qwen3-Next-80B-A3B's share compiled ahead of time for a described v5e:
the WHOLE published round (three minutes on every core), then its two
kinds of mixer, its experts and the rule's kernel at the published shapes
(in tests/test_tpu_lowering.py until PR 46;
tests/test_v3_granite_lowering.py says why they are here)."""

import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from lm_family import walked_names
from test_tpu_lowering import (  # noqa: F401  (v5e: the fixture)
    EXPERTS, PARENT_PUBLISHED_ROUNDS, _abstract, _block_gradient, _cfg,
    _described_layer, _experts_gradient, _lowered_sha, v5e)

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
    3.66 GB of temporaries (3.98 until PR 49 ran a block's delta net a
    peer at a time); every grouped product, attention core and delta rule
    a kernel, and no `triangular_solve`; inside a block both mixers under
    `peer_walk`, the router and the experts outside it."""
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
    lowered = jax.jit(sim._round_step_raw).lower(*args)
    # the text PR 49 gave it (the delta net under the walk), Mosaic bodies
    # aside
    assert _lowered_sha(lowered) == PARENT_PUBLISHED_ROUNDS["lm_tokens_qwen3next"]
    compiled = lowered.compile()
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
    # a block's mixers, a peer at a time: the rule's kernels, the conv and
    # the gated norm carry the walk in their `op_name`; what the block is
    # there for does not
    walked = walked_names(hlo)
    for scope in ("gdn_rule", "gdn_conv", "gdn_gate", "attn_core"):
        assert any(scope in name for name in walked), scope
    for scope in ("lm_experts", "lm_router"):
        assert not any(scope in name for name in walked), scope
    kernels = [line for line in hlo.splitlines() if "/round_grad/" in line
               and "delta_rule_" in line
               and 'custom_call_target="tpu_custom_call"' in line]
    assert kernels and all("peer_walk" in line and "f32[1,16,32,128,128]"
                           in line for line in kernels)  # ONE window a call
    assert "delta_rule_forward" in hlo and "delta_rule_backward" in hlo


# -------- the delta rule, heads of 256, experts of 2,048 x 512 (PRs 38, 39)


def test_the_delta_net_mixer_at_the_published_shapes_compiles(v5e):
    """One gated delta-net mixer of the published size as a peer sends it
    (1 window of 1,024 tokens: 16 chunks of 64, 16 key heads serving 32
    value heads of 128, bfloat16) under `jax.checkpoint` and `jax.grad`
    compiles for the v5e under x64: the unit-lower-triangular solve and
    its transpose lower, and nothing of it is 64 bits wide."""
    from biscotti_tpu.models import qwen3_next

    cfg = qwen3_next.PRESETS["qwen3_next_fedlora"]
    compiled = _block_gradient(
        lambda h, f, a: qwen3_next._delta_net(cfg, h, f, a),
        _described_layer(v5e, qwen3_next.qwen3_next_model, cfg, 0))
    hlo = compiled.as_text()
    for scope in ("gdn_proj", "gdn_conv", "gdn_rule", "gdn_gate"):
        assert scope in hlo, scope
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]


def test_the_gated_attention_at_heads_of_256_takes_the_kernel(v5e):
    """A gated attention layer of the published size (16 query heads on 2
    key/value heads of 256 | 256: eight query heads a key/value head, the
    widest head and the largest group so far) under `jax.checkpoint` and
    `jax.grad`: `blocks` finds a block inside the kernels' VMEM rule, so
    the core is ops/attention.py's kernel and no float32 array of the
    scores' size [16, 1024, 1024] is made."""
    from biscotti_tpu.models import qwen3_next
    from biscotti_tpu.ops import attention

    cfg = qwen3_next.PRESETS["qwen3_next_fedlora"]
    assert attention.blocks(8, 1024, 256, jnp.bfloat16) == (128, 128)
    assert qwen3_next.attention_plan(cfg, 1024) == {"fused": 1,
                                                    "block_share": 0.5625}
    hlo = _block_gradient(
        lambda h, f, a: qwen3_next._attention(cfg, h, f, a),
        _described_layer(v5e, qwen3_next.qwen3_next_model, cfg,
                         3)).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert 2 <= len(calls) <= 3, len(calls)
    assert any("f32[1,2,8,1024,256]" in c for c in calls)     # the result
    assert any("bf16[1,2,8,1024,256]" in c for c in calls)    # q, dq
    square = re.compile(r"f32\[([\d,]*1024,1024)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 1024 * 1024]
    assert not made, made[:5]


def test_experts_of_2048_by_512_take_the_kernel_under_half_a_tile(v5e):
    """`held_experts` at Qwen3-Next's published shapes (a peer block of 3:
    3,072 tokens, ten a token, 128 of 512 experts held, H = 2,048, F = 512,
    bfloat16): a group is sent 60 rows, under half of the smallest row
    tile there is, and every grouped product is still
    ops/grouped_matmul.py's, whole weights a column tile."""
    from biscotti_tpu.ops import grouped_matmul

    n, (k, e, total, h, f) = 3072, EXPERTS["qwen3_next"]
    assert n * k / total == 60.0
    tile = grouped_matmul.row_tile(n * k / total)
    assert tile == grouped_matmul.ROW_TILES[0] == 128
    for rows in (n * k // 2, n * k):  # the cut buffer and the uncut one
        assert grouped_matmul.column_tile(rows, h, f, jnp.bfloat16,
                                          tile) == 512
        assert grouped_matmul.column_tile(rows, f, h, jnp.bfloat16,
                                          tile) == 1024
    compiled = _experts_gradient(v5e[0], "qwen3_next", remat=True)
    hlo = compiled.as_text()
    assert "ragged-dot" not in hlo
    lines = [line.strip() for line in hlo.splitlines()]
    calls = [line for line in lines
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 18  # 9 a side: primal 3, recomputed 3, transposed 3
    stack = r" = bf16\[128,(2048,512|512,2048)\]"
    made = [line[:160] for line in lines if re.search(stack, line)
            and "parameter(" not in line and "get-tuple-element(" not in line]
    assert not made, made[:5]
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9


def test_the_delta_rule_at_the_published_shapes_is_the_kernel(v5e):
    """The same mixer's gradient, read for the rule (PR 39): under scope
    `gdn_rule` there are ops/delta_rule.py's three `tpu_custom_call`s (the
    forward pass's and the recomputed forward's, which both write the
    chunks' entry states: under `jax.grad` the first is the same call, and
    a custom call's unread result is still written; and the backward's,
    which reads them) and each is booked under `gdn_rule`, the LAST scope
    of its `op_name`, where a device trace's reader looks; nothing of a
    chunk's system is an array any more: no float32 `[..., 64, 256]`
    right side or solution, no `[..., 64, 64]` decay or system, and no
    `while` (the `jax.numpy` form's 16 carried steps) anywhere."""
    from biscotti_tpu.models import qwen3_next

    cfg = qwen3_next.PRESETS["qwen3_next_fedlora"]
    described = _described_layer(v5e, qwen3_next.qwen3_next_model, cfg, 0)
    assert described[1].info["gdn_rule"]["kernel"] == 1
    hlo = _block_gradient(
        lambda h, f, a: qwen3_next._delta_net(cfg, h, f, a),
        described).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3, len(calls)
    scopes = re.compile("|".join(qwen3_next.SCOPES))
    for call in calls:
        name = re.search(r'op_name="([^"]*)"', call).group(1)
        assert scopes.findall(name)[-1] == "gdn_rule", name
    assert all("f32[1,16,32,128,128]" in c for c in calls)  # entry states
    assert any("bf16[1,1024,2048]" in c for c in calls)     # q as it comes
    chunk = re.compile(r"f32\[[\d,]*,64,(?:64|256)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            if chunk.search(line.split(" = ")[-1].split("(")[0])]
    assert not made, made[:5]
    assert " while(" not in hlo and "triangular" not in hlo
