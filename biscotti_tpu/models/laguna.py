"""Laguna-S-2.1 (poolside, `model_type` "laguna") as a Biscotti model: a
frozen share of its sparse-expert, window/full-attention decoder, with
rank-r adapters on q, k, v and o whose `B` factors are what the peers
train, commit and aggregate (the FFA-LoRA form, "Improving LoRA in
Privacy-preserving Federated Learning", ICLR 2024: `A` frozen and shared,
so that the sum of the peers' updates IS the update of the sum).

Source: https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json.
What that file does not state (marked † in benchmark/configs/
laguna_s_2.1_fedlora.json, `assumed`): pre-norm blocks, rotate-half
rotary, no q/k norm, a sigmoid per-head output gate from the normed input,
softmax routing before the top-k, no gate on the shared expert, no
auxiliary load loss.

    h0 = E[tokens];  per layer:
      x = RMSNorm(h);  q, k, v = x Wq, x Wk, x Wv  (+ adapters)
      rotary on the first rho*head_dim dimensions (sliding: rho 1, theta 1e4;
        full: rho 0.5, theta 5e5, YaRN frequencies, cos/sin x attention_factor)
      o = softmax(q k^T / sqrt(head_dim) + mask) v;  o *= sigmoid(x Wgate) a head
      h += concat(o) Wo (+ adapter)
      x = RMSNorm(h);  layer 0: h += SwiGLU_dense(x);  else
      h += Shared(x) + sum over the top-k experts HELD HERE of
           scale * p_e / sum_topk p * Expert_e(x)           (ops/moe.py)
    logits = RMSNorm(h) W_head over the held rows of the vocabulary

The attention core (the `softmax(...) v` line) is ops/attention.py's: where
the shapes allow (heads of 128, windows of whole blocks: the published
size) ONE fused, blocked kernel a call, forward and backward, that keeps
the scores in VMEM a (query block, key block) tile at a time and never
visits a pair of blocks the mask hides; elsewhere (the tiny preset) the
`einsum` form that writes the float32 scores [heads, T, T] to HBM.
`attention_plan` says which, from the shapes alone.

The trainable tree is {"layers": [{"k", "o", "q", "v"}: B [r, out]]}; the
frozen tree holds everything else, in `dtype` (bfloat16 at the published
size), and every product runs in that dtype with float32 accumulation.

The peer axis meets the expert dispatch ONCE a block: all the peers of a
block share the round's weights, so their tokens go through the frozen
stack as one batch (one router, one sort, one grouped product a layer),
and only the adapters' `B` carry a peer axis: the gradient of the SUM of
the peers' losses with respect to `B[P, r, out]` is each peer's own
gradient, because no operation mixes two windows.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.models.lm import frozen_count  # noqa: F401  (its callers')
from biscotti_tpu.ops import attention, moe

# scopes inside `round_grad` a device trace is read by (a second
# vocabulary beside parallel/sim.STAGES; docs/OBSERVABILITY.md);
# `peer_walk`: the loop of `lm.peer_at_a_time` itself (slices, stacked
# results and residuals)
SCOPES = ("lm_embed", "lm_attention", "lm_router", "lm_experts", "lm_dense",
          "lm_head_loss", "peer_clip", "peer_walk")
# what `lm_attention` is made of, each opened INSIDE it and read under
# SCOPES + SUBSCOPES (under SCOPES alone an instruction's last token is
# still `lm_attention`, and `lm_attention_ms.device` reads what it read):
# the block norm; the adapted q, k, v products; the rotation and its
# concatenations; the reshapes, head-major transposes and casts; the
# `attention.attention` call alone; the per-head gate and `W_o` with its
# adapter. None is part of another or a frozen leaf's name (`attn_norm`)
SUBSCOPES = ("attn_norms", "attn_in", "attn_rotary", "attn_layout",
             "attn_core", "attn_out")


@dataclass(frozen=True)
class LagunaConfig:
    hidden: int
    head_dim: int
    kv_heads: int
    heads: Tuple[int, ...]          # query heads, layer by layer
    layer_types: Tuple[str, ...]    # "full" | "sliding"
    dense_layers: Tuple[int, ...]   # layers whose MLP is the dense SwiGLU
    window: int
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int                # the router's width (published)
    experts_held: int               # experts first_expert .. + held, here
    top_k: int
    routed_scale: float
    vocab: int                      # rows of the vocabulary held here
    rope_full: dict = field(hash=False, compare=False, default=None)
    rope_sliding: dict = field(hash=False, compare=False, default=None)
    first_expert: int = 0
    eps: float = 1e-6
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"

    @property
    def layers(self) -> int:
        return len(self.layer_types)


ROPE_FULL = {"rope_theta": 500000.0, "factor": 128.0,
             "original_max_position_embeddings": 8192, "beta_fast": 32.0,
             "beta_slow": 1.0, "attention_factor": 1.4852030263919618,
             "partial_rotary_factor": 0.5}
ROPE_SLIDING = {"rope_theta": 10000.0, "partial_rotary_factor": 1.0}

PRESETS = {
    # the published widths; layers 0-4 (the leading dense one and one whole
    # period), 64 of the 256 experts and a quarter of the vocabulary: one
    # chip's share when four chips share each layer
    "laguna_s_fedlora": LagunaConfig(
        hidden=3072, head_dim=128, kv_heads=8, heads=(48, 72, 72, 72, 48),
        layer_types=("full", "sliding", "sliding", "sliding", "full"),
        dense_layers=(0,), window=512, dense_width=12288, expert_width=1024,
        shared_width=1024, num_experts=256, experts_held=64, top_k=10,
        routed_scale=2.5, vocab=25088, rope_full=ROPE_FULL,
        rope_sliding=ROPE_SLIDING),
    # the same mechanism at the CPU tests' size: every kind of layer, the
    # 48/72-style head split, 4 of 16 experts held, float32 throughout
    "laguna_tiny": LagunaConfig(
        hidden=32, head_dim=8, kv_heads=2, heads=(4, 6, 4),
        layer_types=("full", "sliding", "full"), dense_layers=(0,),
        window=4, dense_width=48, expert_width=8, shared_width=8,
        num_experts=16, experts_held=4, top_k=3, routed_scale=2.5, vocab=64,
        rope_full=dict(ROPE_FULL, original_max_position_embeddings=8,
                       factor=4.0),
        rope_sliding=ROPE_SLIDING, rank=2, alpha=4.0, dtype="float32"),
}


# ------------------------------------------------------------------ rotary


def rotary_tables(cfg: LagunaConfig, kind: str, length: int):
    """(cos, sin) float32[T, rot / 2] and the rotated width `rot`."""
    rope = cfg.rope_full if kind == "full" else cfg.rope_sliding
    rot = int(cfg.head_dim * rope["partial_rotary_factor"])
    return lm.yarn_tables(rot, rope, length) + (rot,)


# ----------------------------------------------------------------- forward


def _attention(cfg, at, h, frozen, adapters):
    """The attention block of layer `at` on h [P, b, T, H]."""
    kind, n = cfg.layer_types[at], cfg.heads[at]
    p, b, t, _ = h.shape
    dh, kv = cfg.head_dim, cfg.kv_heads
    lora = frozen["lora_a"]
    scope = jax.named_scope

    def heads(name, count):
        with scope("attn_in"):
            y = lm.adapted(cfg, x, frozen["w" + name], lora[name],
                           adapters[name])
        with scope("attn_layout"):
            return y.reshape(p * b, t, count, dh).transpose(0, 2, 1, 3)

    with scope("lm_attention"):
        with scope("attn_norms"):
            x = lm.rms(h, frozen["attn_norm"], cfg.eps)
        q, k, v = heads("q", n), heads("k", kv), heads("v", kv)
        with scope("attn_rotary"):
            cos, sin, rot = rotary_tables(cfg, kind, t)
            q = lm.rotate_half(q, cos, sin, rot)
            k = lm.rotate_half(k, cos, sin, rot)
        with scope("attn_layout"):
            dtype = frozen["wq"].dtype
            q = q.reshape(p * b, kv, n // kv, t, dh).astype(dtype)
            k, v = k.astype(dtype), v.astype(dtype)
        with scope("attn_core"):
            out = attention.attention(q, k, v,
                                      t if kind == "full" else cfg.window)
        with scope("attn_out"):
            gate = jax.nn.sigmoid(lm.mm(x, frozen["wgate"]))  # [P, b, T, n]
        with scope("attn_layout"):                            # [W, T, n, dh]
            out = out.reshape(p * b, n, t, dh).transpose(0, 2, 1, 3)
        with scope("attn_out"):
            out = out * gate.reshape(p * b, t, n)[..., None]
            out = out.reshape(p, b, t, n * dh)
            return lm.adapted(cfg, out, frozen["wo"], lora["o"],
                              adapters["o"])


def attention_plan(cfg: LagunaConfig, length: int) -> dict:
    """How `_attention` is built on windows of `length`, from the shapes
    alone: `fused` 1 where every layer's core is ops/attention.py's kernel
    (0: some layer's is the `einsum` form), and `block_share`, the part of
    the layers' [T, T] scores that is computed at all: the (query block,
    key block) pairs the kernel visits over all pairs, a layer the `einsum`
    form runs counting whole."""
    shares = []
    for kind, n in zip(cfg.layer_types, cfg.heads):
        block = attention.blocks(n // cfg.kv_heads, length, cfg.head_dim,
                                 cfg.dtype)
        shares.append(block and attention.block_share(
            length, length if kind == "full" else cfg.window, *block))
    return {"fused": int(all(shares)),
            "block_share": sum(s or 1.0 for s in shares) / len(shares)}


def _mlp(cfg, at, h, frozen):
    """The MLP block of layer `at` on h [N, H]: (result, the dispatch's
    counts, the router's (experts, probabilities)); the last two None on a
    dense layer."""
    x = lm.rms(h, frozen["mlp_norm"], cfg.eps)
    if at in cfg.dense_layers:
        with jax.named_scope("lm_dense"):
            return lm.swiglu(x, frozen["dense"]), None, None
    with jax.named_scope("lm_router"):
        experts, coef, probs = moe.route(x, frozen["router"], cfg.top_k,
                                         cfg.routed_scale)
    with jax.named_scope("lm_dense"):
        shared = lm.swiglu(x, frozen["shared"])
    with jax.named_scope("lm_experts"):
        routed, counts = moe.held_experts(x, experts, coef,
                                          frozen["experts"],
                                          cfg.first_expert, cfg.num_experts)
    return shared + routed, counts, (experts, probs)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_as(cfg, at, h, frozen, adapters):
    """A layer built as layer `at` is. Jitted, so that a round traces the
    three kinds of layer and not the five layers: the walked attention (a
    loop, rematerialised and transposed) is slow to trace, and set-up
    pays it once a layer otherwise (`setup_s`; PERF.md section 6, PR 35)."""
    walked = lm.peer_at_a_time(
        lambda h, adapters: _attention(cfg, at, h, frozen, adapters),
        h, adapters)
    with jax.named_scope("lm_attention"):  # the residual is the block's too
        h = h + walked
    out, counts, picks = _mlp(cfg, at, h.reshape(-1, h.shape[-1]), frozen)
    return h + out.reshape(h.shape), counts, picks


def _layer(cfg, at, h, frozen, adapters):
    """Layer `at`, as the first layer of its kind (attention, heads, MLP)."""
    def kind(i):
        return cfg.layer_types[i], cfg.heads[i], i in cfg.dense_layers

    first = next(i for i in range(cfg.layers) if kind(i) == kind(at))
    return _layer_as(cfg, first, h, frozen, adapters)


# (h [P, b, T, H], counts, picks) of tokens int32[P, b, T] under adapters
# with a peer axis: lm.decoder's walk over this model's layers
hidden_states = lm.decoder(_layer)


def routing(cfg, params, tokens, frozen):
    """The router's choices for `tokens` int32[b, T] under adapters
    `params` (no peer axis): experts int32[L, b*T, k] and probabilities
    float32[L, b*T, E_all], one row a sparse layer, in layer order."""
    return lm.routing(hidden_states, cfg, params, tokens, frozen)


# ------------------------------------------------------------------- model


def _shapes(cfg: LagunaConfig):
    """({path: (shape, fan_in)} of the frozen leaves, layer by layer,
    [{name: shape}] of the trained ones)."""
    hdim, dh, r = cfg.hidden, cfg.head_dim, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), 1),
              "head": ((hdim, cfg.vocab), hdim),
              "final_norm": ((hdim,), 0), "layers": []}
    trained = []
    for at in range(cfg.layers):
        n, kv = cfg.heads[at] * dh, cfg.kv_heads * dh
        layer = {"attn_norm": ((hdim,), 0), "mlp_norm": ((hdim,), 0),
                 "wq": ((hdim, n), hdim), "wk": ((hdim, kv), hdim),
                 "wv": ((hdim, kv), hdim), "wo": ((n, hdim), n),
                 "wgate": ((hdim, cfg.heads[at]), hdim),
                 "lora_a": {"q": ((hdim, r), hdim), "k": ((hdim, r), hdim),
                            "v": ((hdim, r), hdim), "o": ((n, r), n)}}

        if at in cfg.dense_layers:
            layer["dense"] = lm.swiglu_shapes(hdim, cfg.dense_width)
        else:
            layer["router"] = ((hdim, cfg.num_experts), hdim)
            layer["shared"] = lm.swiglu_shapes(hdim, cfg.shared_width)
            layer["experts"] = lm.swiglu_shapes(hdim, cfg.expert_width,
                                                (cfg.experts_held,))
        frozen["layers"].append(layer)
        trained.append({"q": (r, n), "k": (r, kv), "v": (r, kv),
                        "o": (r, hdim)})
    return frozen, trained


def laguna_model(name: str, cfg: LagunaConfig, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)
    dtype = jnp.dtype(cfg.dtype)

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak, the widest layer's recomputation and backward: the
        attention's scores and their cotangents in float32 (2 arrays of
        [heads, T, T]), the sorted expert rows (in `dtype`) and what the
        grouped products make of them (float32), the logits and their
        cotangents, a dozen hidden states. Within a fifth of what the
        compiled round's memory analysis read a peer at the published
        size (0.9 GB; PERF.md section 6, PR 27) WHILE THE SCORES WERE
        HELD. Since PR 30 they are not, where ops/attention.py's kernel
        runs: the first term (604 MB of a peer's 1.15 GB at the published
        size) over-counts a peer by them, and is KEPT: without it
        `peer_step.peer_block` takes 7 peers where 3 (the round then
        compiles at 4.59 GB of temporaries where 2.24 and fits), and on
        the chip the round of 7 is slower, 1,207 ms against 1,110 (PERF.md
        section 6, PR 35): a grouped call on 280-row groups takes row
        tiles of 512 and computes 2.8 rows for each one held, and the
        gathers and scatters around it stream 36,000-row buffers from
        HBM. Count the scores out when ops/moe.py's row tile and movement
        are made for the larger block (ROADMAP A8c), not before."""
        t = batch * length
        return (2 * 4 * max(cfg.heads) * batch * length * length
                + t * cfg.top_k * cfg.hidden * (4 + dtype.itemsize)
                + 2 * 4 * t * cfg.vocab + 12 * 4 * t * cfg.hidden)

    plan = attention_plan(cfg, length)
    return lm.lm_model(name, cfg, length,
                       (frozen_shapes, {"layers": trained_shapes}),
                       hidden_states, step_bytes,
                       {"attention": plan,
                        "gauges": lm.attention_gauges(plan)})
