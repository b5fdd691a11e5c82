"""What the language models of this package share (models/laguna.py,
models/deepseek_v2.py, models/granite_hybrid.py, models/qwen3_next.py,
models/mimo_v2.py): a decoder whose frozen base is held once beside rank-r
adapters `B [r, out]`, which are what the peers train, commit and
aggregate (the FFA-LoRA form:
`A` frozen and shared, so that the sum of the peers' updates IS the update
of the sum).

  the arithmetic   `rms` (its weight the scale, or the scale less one),
                   `mm`, `swiglu`, `adapted`: every product in the
                   frozen weights' dtype with float32 accumulation, norms
                   in float32; what the two hybrids' mixers share:
                   `causal_conv` (with a bias or none), `gated_norm` (the
                   gate before the norm or after it)
  rotary           `yarn_tables`: (cos, sin) of plain or YaRN-scaled
                   frequencies, made on the host in float64;
                   `rotate_half` on the first dimensions of a head
  the peer axis    `decoder`: a model's `hidden_states` takes adapters with
                   a leading peer axis on every leaf and the peers' windows
                   as ONE batch (module doc of models/laguna.py), a layer
                   at a time, each rematerialised; `peer_losses` closes it
                   with the head and the loss
  the `Model`      `lm_model`: init (seeded non-zero adapters), the frozen
                   tree drawn leaf by leaf on the device, apply / loss /
                   peer_losses over the model's own `hidden_states`
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from biscotti_tpu.models.base import make_model


def yarn_tables(rot: int, rope: dict, length: int):
    """(cos, sin) float32[T, rot / 2] of a rotary embedding over `rot`
    dimensions: `rope` holds `rope_theta` and, for YaRN, `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow` (the
    linear ramp between the floor and the ceiling of the correction
    dimensions, as transformers' `_compute_yarn_parameters`) and
    `attention_factor`, which multiplies cos and sin."""
    base = float(rope["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    factor = 1.0
    if "factor" in rope:  # YaRN
        original = rope["original_max_position_embeddings"]

        def correction_dim(rotations):
            return (rot * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        inv = inv / rope["factor"] * ramp + inv * (1.0 - ramp)
        factor = float(rope["attention_factor"])
    angles = np.arange(length, dtype=np.float64)[:, None] * inv[None, :]
    return ((np.cos(angles) * factor).astype(np.float32),
            (np.sin(angles) * factor).astype(np.float32))


def rotate_half(x, cos, sin, rot):
    """Rotate-half rotary on the first `rot` of the last axis; x [..., T,
    head_dim], cos/sin [T, rot / 2]."""
    turned, rest = x[..., :rot], x[..., rot:]
    a, b = turned[..., :rot // 2], turned[..., rot // 2:]
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([turned, rest], axis=-1)


def rms(x, weight, eps, zero_centred=False):
    """RMSNorm over the last axis in float32; `zero_centred`: the stored
    weight is the scale less one (a norm read as `1 + w`)."""
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    if zero_centred:
        return x * scale * (1.0 + weight.astype(jnp.float32))
    return x * scale * weight.astype(jnp.float32)


def causal_conv(x, weight, bias=None):
    """The causal depthwise conv over the tokens: x float32[W, T, C],
    weight [K, C], bias [C] or None; out_t = bias + sum_i weight[i] x_{t -
    (K-1) + i}, the tokens before the window counting 0."""
    taps, t = weight.shape[0], x.shape[1]
    weight = weight.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(weight[i] * padded[:, i:i + t] for i in range(taps))
    return out if bias is None else bias.astype(jnp.float32) + out


def gated_norm(y, z, weight, eps, gate_first=True):
    """A mixer's gated RMSNorm over the last axis, the gate silu(z) BEFORE
    the norm (`gate_first`: RMSNorm(y * silu(z)) x weight) or after it
    (RMSNorm(y) x weight * silu(z))."""
    if gate_first:
        return rms(y * jax.nn.silu(z), weight, eps)
    return rms(y, weight, eps) * jax.nn.silu(z)


def mm(a, w):
    """a @ w in w's dtype, accumulated in float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def swiglu(x, w):
    hidden = jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"])
    return mm(hidden, w["w_down"])


def swiglu_shapes(hidden: int, width: int, lead=()):
    """{name: (shape, fan_in)} of a SwiGLU's three weights (`lead`: a
    stack of experts)."""
    return {"w_gate": (lead + (hidden, width), hidden),
            "w_up": (lead + (hidden, width), hidden),
            "w_down": (lead + (width, hidden), width)}


def adapted(cfg, x, w, a, b):
    """x W + (alpha / r) (x A) B, B with a peer axis: x [P, b, T, in],
    B [P, r, out]."""
    low = jnp.einsum("pbtr,pro->pbto", mm(x, a).astype(a.dtype),
                     b.astype(a.dtype), preferred_element_type=jnp.float32)
    return mm(x, w) + (cfg.alpha / cfg.rank) * low


def peer_at_a_time(fn, h, adapters):
    """`fn(h, adapters)` of a block h [P, b, T, H] under adapters with a
    peer axis, computed one peer after the other and stacked: the same
    numbers in arrays a P-th the size. A block of peers is there for the
    routed experts (one stream of an expert stack serves the block's
    tokens); the attention's elementwise passes, copies and slices around
    its kernel gain nothing from it and lose the chip's fast memory: the
    compiler keeps an operand of tens of MB there (one peer's
    `f32[128, 1024, 64]` is 32 MB) and streams three peers' from HBM
    (DeepSeek-V2's projections at a block of 3: 2,007 ms a round against
    1,602 at a block of 1; PERF.md section 6, PR 35).

    The walk books its own work: the loop's instructions (a peer's rows
    sliced out, the results and the backward pass's residuals stacked,
    the counters) carry the scope `peer_walk`, which joins the model's
    `SCOPES`. A traced instruction takes the LAST scope of its `op_name`,
    so whatever `fn` computes must open its scope INSIDE `fn`, or it
    reads as the walk's (docs/OBSERVABILITY.md, "Device trace"). A block
    of one peer walks nothing and opens no such scope."""
    if h.shape[0] == 1:
        return fn(h, adapters)
    with jax.named_scope("peer_walk"):
        return jax.lax.map(
            lambda one: fn(*jax.tree.map(lambda a: a[None], one))[0],
            (h, adapters))


def stacked(found):
    found = [f for f in found if f is not None]
    return jax.tree.map(lambda *a: jnp.stack(a), *found) if found else {}


def decoder(layer):
    """`hidden_states(cfg, params, tokens, frozen, remat=True)` of a model
    whose `layer(cfg, at, h, frozen_layer, adapters_layer)` gives (h', the
    dispatch's counts, the router's picks; the last two None on a dense
    layer): (final hidden states [P, b, T, H], counts, picks) of `tokens`
    int32[P, b, T] under adapters with a peer axis (every leaf of `params`
    [P, r, out]), each layer rematerialised in the backward pass; counts
    and picks stacked over the sparse layers, in layer order."""
    def hidden_states(cfg, params, tokens, frozen, remat=True):
        with jax.named_scope("lm_embed"):
            h = embedded(cfg, tokens, frozen)
        counted, picked = [], []
        for at in range(cfg.layers):
            def step(h, layer_frozen, adapters, at=at):
                return layer(cfg, at, h, layer_frozen, adapters)

            if remat:
                step = jax.checkpoint(step)
            h, counts, picks = step(h, frozen["layers"][at],
                                    params["layers"][at])
            counted.append(counts)
            picked.append(picks)
        return h, stacked(counted), stacked(picked)

    return hidden_states


def embedded(cfg, tokens, frozen):
    """float32[..., H]: the tokens' rows of the embedding, times the
    model's `embedding_multiplier` where its config states one."""
    h = frozen["embed"][tokens].astype(jnp.float32)
    scale = getattr(cfg, "embedding_multiplier", None)
    return h if scale is None else scale * h


def logits(cfg, h, frozen):
    """float32[..., V]. A frozen tree without a `head` is a model whose
    head is TIED to its embedding: the one leaf `embed` [V, H] is read
    twice, here contracted over its columns, and the logits divided by the
    config's `logits_scaling`."""
    x = rms(h, frozen["final_norm"], cfg.eps,
            getattr(cfg, "zero_centred", False))
    if "head" in frozen:
        return mm(x, frozen["head"])
    embed = frozen["embed"]
    return jnp.einsum("...h,vh->...v", x.astype(embed.dtype), embed,
                      preferred_element_type=jnp.float32) / cfg.logits_scaling


def peer_losses(hidden_states, cfg, params, tokens, labels, frozen):
    """Each peer's mean next-token cross-entropy over its own windows,
    float32[P], and the dispatch's counts: `params` leaves [P, r, out],
    tokens/labels int32[P, b, T]."""
    h, counts, _ = hidden_states(cfg, params, tokens, frozen)
    with jax.named_scope("lm_head_loss"):
        logp = jax.nn.log_softmax(logits(cfg, h, frozen), axis=-1)
        picked = jnp.sum(jnp.where(
            jnp.arange(logp.shape[-1], dtype=jnp.int32)
            == labels[..., None].astype(jnp.int32), logp, 0.0), axis=-1)
        return -jnp.mean(picked, axis=(1, 2)), counts


def one_peer(tree):
    return jax.tree.map(lambda a: a[None], tree)


def routing(hidden_states, cfg, params, tokens, frozen):
    """The router's choices for `tokens` int32[b, T] under adapters
    `params` (no peer axis): experts int32[L, b*T, k] and probabilities
    float32[L, b*T, E_all], one row a sparse layer, in layer order."""
    return hidden_states(cfg, one_peer(params), tokens[None], frozen,
                         remat=False)[2]


def _is_leaf(node):
    return isinstance(node, tuple) and isinstance(node[0], tuple)


def step_bias(key, shape):
    """A law of a frozen leaf: the inverse softplus of a step log-uniform
    in [1e-3, 1e-1] (Mamba-2's `dt_min`, `dt_max`)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@partial(jax.jit, static_argnames=("shape", "fan_in", "dtype"))
def _draw(key, shape, fan_in, dtype):
    """One frozen leaf, drawn where it will live: norm weights around 1
    (`fan_in` 0), a law of the model's own (`fan_in` a function of (key,
    shape) that gives float32), the rest fan-in scaled normal."""
    if callable(fan_in):
        return fan_in(key, shape).astype(dtype)
    noise = jax.random.normal(key, shape, jnp.float32)
    if fan_in == 0:
        return (1.0 + 0.1 * noise).astype(dtype)
    return (noise / math.sqrt(fan_in)).astype(dtype)


def lm_model(name: str, cfg, length: int, shapes, hidden_states, step_bytes,
             info: dict):
    """The Biscotti `Model` of a decoder on windows of `length` tokens.

    `shapes` = ({path: (shape, fan_in)} of the frozen leaves, the trained
    tree's {path: shape}); `hidden_states(cfg, params, tokens, frozen,
    remat=True)` -> (h [P, b, T, H], counts, picks); `cfg` has `vocab`,
    `eps`, `dtype`."""
    frozen_shapes, trained_shapes = shapes
    dtype = jnp.dtype(cfg.dtype)

    def init(key):
        """Seeded NON-zero adapters (a round's start is zeros, as LoRA's
        `B` starts; tests and the benchmark's checked round draw these)."""
        leaves, treedef = jax.tree.flatten(
            trained_shapes, is_leaf=lambda n: isinstance(n, tuple))
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            0.02 * jax.random.normal(k, shape, jnp.float32)
            for k, shape in zip(keys, leaves)])

    def init_frozen(key):
        """Leaf by leaf, each drawn on the device: never the whole base
        on the host."""
        leaves, treedef = jax.tree.flatten(frozen_shapes, is_leaf=_is_leaf)
        return jax.tree.unflatten(treedef, [
            _draw(jax.random.fold_in(key, i), shape, fan_in, dtype)
            for i, (shape, fan_in) in enumerate(leaves)])

    def losses(params, x, y, frozen):
        return peer_losses(hidden_states, cfg, params, x, y, frozen)

    def apply(params, x, frozen):
        h = hidden_states(cfg, one_peer(params), x[None], frozen,
                          remat=False)[0]
        return logits(cfg, h[0], frozen)

    def loss(params, x, y, frozen):
        return losses(one_peer(params), x[None], y[None], frozen)[0][0]

    return make_model(name, length, cfg.vocab, init, apply, loss,
                      step_rule="clipped_sgd", token_input=True,
                      init_frozen=init_frozen, peer_losses=losses,
                      step_bytes=step_bytes, info=dict(info, config=cfg))


def frozen_count(model) -> int:
    """Parameters in the model's frozen tree, from shapes alone."""
    tree = jax.eval_shape(model.init_frozen, jax.random.PRNGKey(0))
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))


def attention_gauges(plan: dict):
    """The rows of a model's `attention_plan` among its static gauges: what
    every plan states, `shared_key` (0 where it states none) and, where it
    tells its `kinds` of core apart, a row a kind.

    A model declares its static gauges as `info["gauges"]`, rows (name,
    help, value, labels): these beside its own, written where they are
    computed (the model's builder). A value is a number, or a function of
    (the run's starting adapters, the held-out windows, the frozen tree). A
    simulator with a registry publishes the rows before its first round
    and reads them no other way."""
    rows = [
        ("biscotti_lm_attention_fused",
         "1 where the round's attention cores are ops/attention.py's "
         "kernel, 0 the einsum form that writes the scores to HBM",
         plan["fused"], {}),
        ("biscotti_lm_attention_block_share",
         "(query block, key block) pairs of the scores the attention "
         "visits over all pairs, all layers (the einsum form: 1)",
         plan["block_share"], {}),
        ("biscotti_lm_attention_shared_key",
         "1 where the attention core receives a key part once for all "
         "heads beside each head's own (DeepSeek-V2's one rotary key), 0 "
         "where every key is its head's own", plan.get("shared_key", 0), {})]
    for kind, of in plan.get("kinds", {}).items():
        rows += [
            ("biscotti_attn_block_share",
             "(query block, key block) pairs of the scores the attention "
             "core of a KIND of layer visits over all pairs "
             "(models/mimo_v2.py: window | full; the einsum form: 1)",
             of["block_share"], {"kind": kind}),
            ("biscotti_attn_seen_share",
             "scores the mask lets through over the scores of the pairs of "
             "blocks that kind's core visits", of["seen_share"],
             {"kind": kind}),
            ("biscotti_attn_group",
             "query heads a call of that kind's core holds together: a "
             "key/value head's, or a sub-group of them "
             "(ops/attention.group_split)", of["group"], {"kind": kind})]
    return rows
