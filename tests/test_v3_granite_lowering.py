"""Granite-4.0-H-Micro compiled ahead of time for a described v5e: the
WHOLE published round (three minutes on every core: beside
tests/test_runtime.py's live clusters and their 4 s timeouts it cost them
their updates inside tests/test_tpu_lowering.py, PR 33's first whole run),
then the model's sizes and its two kinds of layer at the published shapes
(in tests/test_tpu_lowering.py until PR 46: a file of ONE test is handed
out last by `--dist loadfile`, which gives files out by their number of
tests, and a worker that takes one is handed the next one-test file with
it)."""

import math
import re

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from biscotti_tpu.parallel.sim import Simulator
from test_tpu_lowering import (  # noqa: F401  (v5e: the fixture)
    PARENT_PUBLISHED_ROUNDS, _abstract, _block_gradient, _cfg,
    _described_layer, _lowered_sha, v5e)

HYBRID = dict(dataset="lm_tokens_granite", num_nodes=30, batch_size=1,
              sample_percent=0.7, num_verifiers=3, num_miners=3,
              num_noisers=2, learning_rate=0.1, grad_clip=1.0)


def test_the_published_hybrid_round_compiles_for_v5e(v5e, monkeypatch):
    """The WHOLE round of `granite_h_fedlora.device_round` (30 peers, 21
    sampled, one window each, DP noise, Krum, the held-out windows'
    forward; 40 layers unrolled, each rematerialised) compiles for a
    described v5e with the base NEVER drawn (zeros in its place: the
    compile sees shapes), walks its peers one at a time and fits: the
    base and the stacks as arguments, 3.3 GB of temporaries."""
    from biscotti_tpu.models import lm

    monkeypatch.setattr(lm, "_draw", lambda key, shape, fan_in, dtype:
                        jnp.zeros(shape, dtype))
    sim = Simulator(_cfg(**HYBRID))
    assert sim.num_params == 6410240 and sim.cfg.num_samples == 21
    assert sim.frozen_bytes() == 2 * 3195459328
    assert sim.peer_block == 1
    one = SingleDeviceSharding(v5e[0])
    w, stake = sim.init_state()
    args = (_abstract([w, stake, jnp.asarray(0),
                       jnp.asarray(sim.cfg.seed, jnp.int32)], one)
            + _abstract([sim.x, sim.y], one, stack=True)
            + _abstract([sim.x_val, sim.y_val], one)
            + [jax.tree.map(lambda a: _abstract([a], one)[0], sim.frozen)])
    lowered = jax.jit(sim._round_step_raw).lower(*args)
    # the text it had before the seventh model (PR 48), Mosaic bodies aside
    assert _lowered_sha(lowered) == PARENT_PUBLISHED_ROUNDS["lm_tokens_granite"]
    compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert 6.4e9 < memory.argument_size_in_bytes < 6.5e9
    assert memory.temp_size_in_bytes < 3.6e9
    assert memory.generated_code_size_in_bytes < 0.3e9  # no stack copied
    hlo = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in hlo  # the attention
    for scope in ("ssm_scan", "ssm_proj", "ssm_conv", "ssm_gate",
                  "lm_attention", "lm_dense", "lm_head_loss"):
        assert scope in hlo, scope


# ------------------------------ its sizes and its two kinds of layer (PR 33)


def test_the_hybrids_sizes_from_shapes_alone():
    """d = 6,410,240 and 3,195,459,328 frozen parameters (6.39 GB in
    bfloat16, 39.9% of the chip), the tied embedding counted once, with no
    parameter drawn; the sibling models' plans are the parent's."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    model = model_for_dataset("lm_tokens_granite")
    assert model.num_params == 6410240
    assert lm.frozen_count(model) == 3195459328
    tree = jax.eval_shape(model.init_frozen, jax.random.PRNGKey(0))
    assert {leaf.dtype for leaf in jax.tree.leaves(tree)} == {
        jnp.dtype(jnp.bfloat16)}
    assert model.info["attention"] == {"fused": 1, "block_share": 0.75}
    assert model_for_dataset("lm_tokens").info["attention"] == {
        "fused": 1, "block_share": 0.75}
    assert model_for_dataset("lm_tokens_dsv2").info["attention"] == {
        "fused": 1, "block_share": 0.75, "shared_key": 1}
    # a peer's step holds 2.05 GB by the model's count: a 16 GB chip with
    # 6.39 GB of base and 1.67 GB of deltas standing steps ONE at a time
    from biscotti_tpu.models.peer_step import DEVICE_BYTES, peer_block

    step = model.step_bytes(1)
    assert 2.0e9 < step < 2.1e9
    free = DEVICE_BYTES - 2 * 3195459328 - 4 * (3 * 21 + 2) * 6410240
    assert peer_block(21, step, free) == 1


def test_the_state_space_layer_at_the_published_shapes_compiles(v5e):
    """One Mamba-2 layer of the published size as a peer sends it (1
    window of 1,024 tokens: 4 chunks of 256, 64 heads of 64, state 128,
    bfloat16) under `jax.checkpoint` and `jax.grad` compiles for the v5e
    under x64, and its scan makes no float32 array of the decays' size
    [chunks, heads, 256, 256] more than a handful of times."""
    from biscotti_tpu.models import granite_hybrid

    cfg = granite_hybrid.PRESETS["granite_h_micro_fedlora"]
    compiled = _block_gradient(
        lambda h, f, a: granite_hybrid._layer(cfg, 0, h, f, a),
        _described_layer(v5e, granite_hybrid.granite_hybrid_model, cfg, 0))
    hlo = compiled.as_text()
    assert "ssm_scan" in hlo and "ssm_conv" in hlo and "ssm_gate" in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9
    assert not [line.strip()[:160] for line in hlo.splitlines()
                if "f64[" in line or ("s64[" in line
                                      and "parameter(" not in line)]


def test_the_hybrids_attention_at_the_published_shapes_takes_the_kernel(v5e):
    """An attention layer of the published size (32 query heads on 8
    key/value heads of 64 | 64, no rotary, the scores times 1 / 64) under
    `jax.checkpoint` and `jax.grad`: ops/attention.py's kernel with the
    values' 64 as they are, and no float32 array of the scores' size."""
    from biscotti_tpu.models import granite_hybrid

    cfg = granite_hybrid.PRESETS["granite_h_micro_fedlora"]
    hlo = _block_gradient(
        lambda h, f, a: granite_hybrid._attention(cfg, h, f, a),
        _described_layer(v5e, granite_hybrid.granite_hybrid_model, cfg,
                         5)).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert 2 <= len(calls) <= 3, len(calls)
    assert any("f32[1,8,4,1024,64]" in c for c in calls)     # the result
    assert any("bf16[1,8,4,1024,64]" in c for c in calls)    # q, dq
    square = re.compile(r"f32\[([\d,]*1024,1024)\]")
    made = [line.strip()[:160] for line in hlo.splitlines()
            for dims in square.findall(line)
            if math.prod(int(v) for v in dims.split(",")) > 1024 * 1024]
    assert not made, made[:5]
