"""MiMo-V2.5 (Xiaomi, `model_type` "mimo_v2") as a Biscotti model: a frozen
share of its language model's decoder (sliding-window attention with a
learned sink beside full attention 5 : 1, grouped queries at heads of 192 |
128, a sparse MLP of 256 experts behind a sigmoid router with a choice
bias), with rank-r adapters on each layer's fused `qkv` and on `o` whose `B`
factors are what the peers train, commit and aggregate (models/lm.py: the
FFA-LoRA form).

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json.
What that file does not itself say is listed in benchmark/configs/
mimo_v2.5_fedlora.json (`assumed`): the fused weight laid out [q | k | v]
flat, the value scale in both kinds of layer, no multi-token-prediction
layers and no encoders, the laws the sinks and the choice bias are drawn
from.

    h0 = E[tokens];  layer l (0-based), kappa = full where
    hybrid_layer_pattern[l] == 0, window where 1:
      x = RMSNorm(h);  [q | k | v] = x W_qkv (+ its adapter): 64 query
        heads of 192, kv_kappa key heads of 192, kv_kappa value heads of 128
        (kv_full 4, kv_window 8)
      rotate-half rotary on the first int(0.334 x 192) = 64 dimensions of
        every q and k head, theta 1e7 (full) | 1e4 (window)
      s_ij = q_i . k_j / sqrt(192), j <= i and, in a window layer, i - j <
        128
      full:    p_ij = softmax_j(s_ij)
      window:  p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij')), b_h the
        head's learned sink: it joins the denominator and has no value
      o_i = 0.707 sum_j p_ij v_j;  h += concat(o) W_o (+ its adapter)
      x = RMSNorm(h);  where moe_layer_freq[l] == 0 (layer 0): h +=
        SwiGLU_dense(x);  else s = sigmoid(x W_r) over ALL experts, the
        top_k largest of s + b (b a frozen bias an expert), weighed by
        s_e / sum_chosen s:  h += sum over those HELD HERE of c_e
        Expert_e(x)                                          (ops/moe.py)
    logits = RMSNorm(h) W_head over the held rows of the vocabulary (untied)

The attention core is ops/attention.py's: its kernel at the published
widths (a key/value head's 16 or 8 query heads one at a time,
`attention.group_split`: the whole group's blocks do not fit beside a
2,048-token key/value head), the `einsum` form at the tiny preset's;
`attention_plan` says which and with what blocks, a kind. The routed
experts are ops/moe.py's, 32 groups of 4,096 x 2,048 at the published size.

The trainable tree is {"layers": [{"o", "qkv"}: B [r, out]]}; the frozen
tree holds everything else in `dtype`. A block of peers meets the expert
dispatch ONCE a layer (models/laguna.py's module doc); the attention runs a
peer at a time inside it (`lm.peer_at_a_time`), and only the adapters' `B`
carry the peer axis.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.ops import attention, moe

# scopes inside `round_grad` a device trace is read by (the siblings' names
# where the work is the same; docs/OBSERVABILITY.md); `peer_walk`: the loop
# of `lm.peer_at_a_time` itself
SCOPES = ("lm_embed", "lm_attention", "lm_router", "lm_experts", "lm_dense",
          "lm_head_loss", "peer_clip", "peer_walk")
# what `lm_attention` is made of (models/laguna.py's list, read under
# SCOPES + SUBSCOPES), the core a kind: `attn_core_swa` the call under the
# window and the sink, `attn_core_full` the causal one
SUBSCOPES = ("attn_norms", "attn_in", "attn_rotary", "attn_layout",
             "attn_core_swa", "attn_core_full", "attn_out")
KINDS = ("full", "window")  # hybrid_layer_pattern's 0 and 1


@dataclass(frozen=True)
class MiMoV2Config:
    hidden: int
    heads: int                      # query heads, both kinds of layer
    kv_heads: Tuple[int, int]       # key/value heads: (full, window)
    head_dim: int                   # q's and k's width
    value_dim: int                  # v's
    rotary_factor: float            # partial_rotary_factor
    rope_theta: Tuple[float, float]  # (full, window): rope_theta, swa_...
    window: int                     # sliding_window
    value_scale: float              # attention_value_scale
    pattern: Tuple[int, ...]        # hybrid_layer_pattern, the layers held
    sparse: Tuple[int, ...]         # moe_layer_freq, the layers held
    dense_width: int
    expert_width: int
    num_experts: int                # the router's width (published)
    experts_held: int               # experts first_expert .. + held, here
    top_k: int
    vocab: int                      # rows of the vocabulary held here
    first_expert: int = 0
    eps: float = 1e-5
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"

    @property
    def layers(self) -> int:
        return len(self.pattern)

    @property
    def rotary(self) -> int:
        """The leading dimensions of a head that rotary turns."""
        return int(self.rotary_factor * self.head_dim)

    def kind(self, at: int) -> Tuple[str, bool]:
        """(full | window, whether the MLP is sparse) of layer `at`."""
        return KINDS[self.pattern[at]], bool(self.sparse[at])


PRESETS = {
    # the published widths; layers 0-6 (the leading dense layer and one
    # whole period: window x 4, full, window), 32 of the 256 experts and an
    # eighth of the vocabulary: one chip's share when eight chips share
    # each layer, on the first of eight pipeline stages
    "mimo_v2_fedlora": MiMoV2Config(
        hidden=4096, heads=64, kv_heads=(4, 8), head_dim=192, value_dim=128,
        rotary_factor=0.334, rope_theta=(1e7, 1e4), window=128,
        value_scale=0.707, pattern=(0, 1, 1, 1, 1, 0, 1),
        sparse=(0, 1, 1, 1, 1, 1, 1), dense_width=16384, expert_width=2048,
        num_experts=256, experts_held=32, top_k=8, vocab=19072),
    # the same mechanism at the CPU tests' size: every kind of layer, a
    # window a quarter of the 16-token window, head groups of 4 and 2,
    # rotary on 4 of 12, 4 of 16 experts held, float32
    "mimo_v2_tiny": MiMoV2Config(
        hidden=32, heads=4, kv_heads=(1, 2), head_dim=12, value_dim=8,
        rotary_factor=0.334, rope_theta=(1e7, 1e4), window=4,
        value_scale=0.707, pattern=(0, 1, 1, 0, 1), sparse=(0, 1, 1, 1, 1),
        dense_width=48, expert_width=8, num_experts=16, experts_held=4,
        top_k=3, vocab=64, rank=2, alpha=4.0, dtype="float32"),
}


# ---------------------------------------------------- the frozen leaves' laws


def sink_law(window: int):
    """A window layer's sinks: N(log window, 1), so that a head's sink
    weighs about what its window's keys weigh together under unit-variance
    scores. At N(0, 1) it holds 1 / 200 of a row's mass at a window of 128,
    under the program's own bfloat16 rounding, and the comparison cannot
    tell a sink from none (benchmark/configs/mimo_v2.5_fedlora.json,
    `assumed`)."""
    def law(key, shape):
        return math.log(window) + jax.random.normal(key, shape, jnp.float32)

    return law


def choice_bias(key, shape):
    """The router's choice bias: N(0, 0.05^2), several times the distance
    between neighbouring sigmoid scores around the eighth of 256."""
    return 0.05 * jax.random.normal(key, shape, jnp.float32)


# ----------------------------------------------------------------- forward


def rotary_tables(cfg: MiMoV2Config, kind: str, length: int):
    """(cos, sin) float32[T, rot / 2] of a layer of `kind`."""
    theta = cfg.rope_theta[KINDS.index(kind)]
    return lm.yarn_tables(cfg.rotary, {"rope_theta": theta}, length)


def _operands(cfg, kind, h, frozen, adapters):
    """The core's operands of a layer of `kind` on h [P, b, T, H], from the
    block norm on: q [W, kv, G, T, d], k [W, kv, T, d], v [W, kv, T, e] in
    the frozen weights' dtype."""
    p, b, t, _ = h.shape
    n, kv = cfg.heads, cfg.kv_heads[KINDS.index(kind)]
    d, e = cfg.head_dim, cfg.value_dim
    dtype = frozen["w_qkv"].dtype
    scope = jax.named_scope

    def heads(first, count, width):
        """[W, count, T, width] of `count` heads of the fused product's
        columns, from column `first` on."""
        with scope("attn_layout"):
            y = qkv[..., first:first + count * width]
            return y.reshape(p * b, t, count, width).transpose(0, 2, 1, 3)

    with scope("attn_norms"):
        x = lm.rms(h, frozen["norm"], cfg.eps)
    with scope("attn_in"):  # one fused weight, one adapter
        qkv = lm.adapted(cfg, x, frozen["w_qkv"], frozen["lora_a"]["qkv"],
                         adapters["qkv"])
    q, k = heads(0, n, d), heads(n * d, kv, d)
    v = heads((n + kv) * d, kv, e)
    with scope("attn_rotary"):
        cos, sin = rotary_tables(cfg, kind, t)
        q = lm.rotate_half(q, cos, sin, cfg.rotary)
        k = lm.rotate_half(k, cos, sin, cfg.rotary)
    with scope("attn_layout"):
        return (q.reshape(p * b, kv, n // kv, t, d).astype(dtype),
                k.astype(dtype), v.astype(dtype))


def _core(cfg, kind, q, k, v, frozen):
    """float32[W, kv, G, T, e]: the softmax over the keys a query sees, a
    window layer's with its head's sink in the denominator."""
    if kind == "window":
        with jax.named_scope("attn_core_swa"):
            return attention.attention(
                q, k, v, cfg.window, sink=frozen["sink"].reshape(q.shape[1:3]))
    with jax.named_scope("attn_core_full"):
        return attention.attention(q, k, v, q.shape[-2])


def _attention(cfg, kind, h, frozen, adapters):
    """The attention block of a layer of `kind` on h [P, b, T, H]."""
    p, b, t, _ = h.shape
    n, e = cfg.heads, cfg.value_dim
    scope = jax.named_scope
    with scope("lm_attention"):
        out = _core(cfg, kind, *_operands(cfg, kind, h, frozen, adapters),
                    frozen)
        with scope("attn_layout"):  # [W, T, n, e], the values' scale on it
            out = cfg.value_scale * out.reshape(p * b, n, t, e).transpose(
                0, 2, 1, 3)
        with scope("attn_out"):
            return lm.adapted(cfg, out.reshape(p, b, t, n * e), frozen["wo"],
                              frozen["lora_a"]["o"], adapters["o"])


def attention_plan(cfg: MiMoV2Config, length: int) -> dict:
    """How the attention cores are built on windows of `length`, from the
    shapes alone. `fused` 1 where both kinds' core is ops/attention.py's
    kernel; `block_share` the (query block, key block) pairs the kernel
    visits over all pairs, the layers' mean (the `einsum` form: 1); and a
    kind: `group`, the query heads a call of the kernel holds together (a
    key/value head's, or a sub-group of them: `attention.group_split`),
    its `blocks`, its `block_share`, and `seen_share`, the share of the
    visited pairs' scores that the mask lets through."""
    kinds = {}
    for at, kind in enumerate(KINDS):
        g = cfg.heads // cfg.kv_heads[at]
        window = min(cfg.window, length) if kind == "window" else length
        shape = (length, cfg.head_dim, cfg.dtype, cfg.value_dim)
        split = attention.group_split(g, *shape)
        block = split and attention.blocks(g // split, *shape)
        share = attention.block_share(length, window, *block) if block \
            else 1.0
        # pairs (i, j) with 0 <= i - j < window, of `length`^2
        seen = (window * length - window * (window - 1) // 2) / length ** 2
        kinds[kind] = {"group": g // split if split else g,
                       "blocks": tuple(block) if block else (),
                       "block_share": share, "seen_share": seen / share}
    shares = [kinds[KINDS[kind]]["block_share"] for kind in cfg.pattern]
    return {"fused": int(all(k["blocks"] for k in kinds.values())),
            "block_share": sum(shares) / len(shares), "kinds": kinds}


def _mlp(cfg, sparse, h, frozen):
    """The MLP block on h [N, H]: (result, the dispatch's counts, the
    router's (experts, what they were chosen by)); the last two None on a
    dense layer. No shared expert: the held experts' part is the whole."""
    x = lm.rms(h, frozen["mlp_norm"], cfg.eps)
    if not sparse:
        with jax.named_scope("lm_dense"):
            return lm.swiglu(x, frozen["dense"]), None, None
    with jax.named_scope("lm_router"):
        experts, coef, chosen_by = moe.route(x, frozen["router"], cfg.top_k,
                                             1.0, bias=frozen["router_bias"])
    with jax.named_scope("lm_experts"):
        routed, counts = moe.held_experts(x, experts, coef,
                                          frozen["experts"],
                                          cfg.first_expert, cfg.num_experts)
    return routed, counts, (experts, chosen_by)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_of(cfg, kind, h, frozen, adapters):
    """A layer of `kind` = (full | window, sparse). Jitted, so that a round
    traces the three kinds of layer and not the seven layers
    (models/laguna.py:_layer_as)."""
    walked = lm.peer_at_a_time(
        lambda h, adapters: _attention(cfg, kind[0], h, frozen, adapters),
        h, adapters)
    with jax.named_scope("lm_attention"):  # the residual is the block's too
        h = h + walked
    out, counts, picks = _mlp(cfg, kind[1], h.reshape(-1, h.shape[-1]),
                              frozen)
    return h + out.reshape(h.shape), counts, picks


def _layer(cfg, at, h, frozen, adapters):
    return _layer_of(cfg, cfg.kind(at), h, frozen, adapters)


# (h [P, b, T, H], counts, picks) of tokens int32[P, b, T] under adapters
# with a peer axis: lm.decoder's walk over this model's layers
hidden_states = lm.decoder(_layer)


def routing(cfg, params, tokens, frozen):
    """`lm.routing` of this model: experts int32[L, b*T, k] and what they
    were chosen by (s + b) float32[L, b*T, E_all] of `tokens` int32[b, T],
    one row a sparse layer, in layer order."""
    return lm.routing(hidden_states, cfg, params, tokens, frozen)


def sink_mass(cfg, params, tokens, frozen):
    """The mean probability a query of `tokens` int32[b, T] gives its
    head's sink, over the window layers, their heads and the tokens: what
    of a row's softmax reaches no value. A diagnostic beside the round's
    path: each window layer's core once more on values of ones, whose
    result is what the row's keys DO collect."""
    masses = []

    def layer(cfg, at, h, frozen_layer, adapters):
        if cfg.kind(at)[0] == "window":
            q, k, v = _operands(cfg, "window", h, frozen_layer, adapters)
            kept = _core(cfg, "window", q, k, jnp.ones_like(v), frozen_layer)
            masses.append(1.0 - jnp.mean(kept))
        return _layer(cfg, at, h, frozen_layer, adapters)

    lm.decoder(layer)(cfg, lm.one_peer(params), tokens[None], frozen,
                      remat=False)
    return jnp.mean(jnp.stack(masses))


# ------------------------------------------------------------------- model


def _widths(cfg: MiMoV2Config, kind: str):
    """{projection: (in, out)} of a layer's adapted projections."""
    kv = cfg.kv_heads[KINDS.index(kind)]
    return {"qkv": (cfg.hidden, (cfg.heads + kv) * cfg.head_dim
                    + kv * cfg.value_dim),
            "o": (cfg.heads * cfg.value_dim, cfg.hidden)}


def _shapes(cfg: MiMoV2Config):
    """({path: (shape, fan_in or law)} of the frozen leaves, layer by
    layer, [{name: shape}] of the trained ones)."""
    hdim, r = cfg.hidden, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), 1),
              "head": ((hdim, cfg.vocab), hdim),
              "final_norm": ((hdim,), 0), "layers": []}
    trained = []
    for at in range(cfg.layers):
        kind, sparse = cfg.kind(at)
        widths = _widths(cfg, kind)
        layer = {"norm": ((hdim,), 0), "mlp_norm": ((hdim,), 0),
                 "w_qkv": (widths["qkv"], hdim),
                 "wo": (widths["o"], widths["o"][0]),
                 "lora_a": {name: ((fan_in, r), fan_in)
                            for name, (fan_in, _) in widths.items()}}
        if kind == "window":
            layer["sink"] = ((cfg.heads,), sink_law(cfg.window))
        if sparse:
            layer["router"] = ((hdim, cfg.num_experts), hdim)
            layer["router_bias"] = ((cfg.num_experts,), choice_bias)
            layer["experts"] = lm.swiglu_shapes(hdim, cfg.expert_width,
                                                (cfg.experts_held,))
        else:
            layer["dense"] = lm.swiglu_shapes(hdim, cfg.dense_width)
        frozen["layers"].append(layer)
        trained.append({name: (r, out) for name, (_, out) in widths.items()})
    return frozen, trained


def mimo_v2_model(name: str, cfg: MiMoV2Config, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak: the float32 logits over the held vocabulary, their
        log-softmax and their cotangent; every layer's input, kept for its
        recomputation; the widest layer's fused `qkv` product, its
        head-major q, k, v (float32 and `dtype`) and their cotangents; a
        token's `top_k` expert rows in the sorted buffers, forward and
        backward. 1.71 GB at the published size on 2,048 tokens, a third of
        what the chip has free beside the base: `peer_step.peer_block`
        takes ONE peer a block, at which the round compiles for a described
        v5e at 2.22 GB of temporaries; a block of 3 does not compile at all
        (the compiler's own scoped-VMEM fault: ROADMAP B15d), so the
        formula has not been held to a second compiled count as the
        siblings' were."""
        t = batch * length
        wide = max(out for kind in KINDS
                   for _, out in [_widths(cfg, kind)["qkv"]])
        return (t * 4 * (3 * cfg.vocab + cfg.layers * cfg.hidden + 4 * wide)
                + t * 2 * wide * dtype.itemsize
                + t * cfg.top_k * cfg.hidden * (4 + dtype.itemsize))

    mimo = lm.lm_model(name, cfg, length,
                       (frozen_shapes, {"layers": trained_shapes}),
                       hidden_states, step_bytes,
                       {"attention": attention_plan(cfg, length),
                        "sink_mass": jax.jit(functools.partial(sink_mass,
                                                               cfg))})
    # the sink's row takes the jitted function `info` holds as its value,
    # so the rows are set once that stands
    mimo.info["gauges"] = lm.attention_gauges(mimo.info["attention"]) + [
        ("biscotti_attn_sink_mass",
         "mean probability a query of the held-out windows gives its head's "
         "learned sink under the run's starting weights, window layers "
         "(what of a row's softmax reaches no value)",
         mimo.info["sink_mass"], {})]
    return mimo
