"""DeepSeek-V2 (deepseek-ai, `model_type` "deepseek_v2") as a Biscotti
model: a frozen share of its latent-attention (MLA), group-routed
sparse-expert decoder, with rank-r adapters on the five attention
projections whose `B` factors are what the peers train, commit and
aggregate (models/lm.py: the FFA-LoRA form).

Source: https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json;
every equation below is in that file's keys. Departures, each stated in
benchmark/configs/deepseek_v2_fedlora.json (`assumed`): no auxiliary
balance loss (`seq_aux` is a training-time term), what the experts held
elsewhere would add is left out (ops/moe.py).

    h0 = E[tokens];  per layer (pre-norm, rms_norm_eps 1e-6):
      x = RMSNorm(h)
      c_q = RMSNorm(x W_qa)                    [q_rank];   q = c_q W_qb ->
            heads x (nope + rope)
      [c_kv | k_r] = x W_kva                   [kv_rank | rope]
      c_kv = RMSNorm(c_kv);  [k_nope | v] = c_kv W_kvb -> heads x (nope + v)
      k = [k_nope | rot(k_r)], rot(k_r) the SAME for every head
      rot: rotary on the rope dimensions in interleaved pairs (2i, 2i + 1),
           theta 10,000, YaRN frequencies; cos, sin x
           mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1;
           in place (ops/rotary.py): q's and the key's turned dimensions
           stay where they were, so every score is that of the pairs
      o = softmax(s q k^T + causal) v,
          s = (nope + rope)^-0.5 x m^2,  m = 0.1 mscale_all_dim ln factor + 1
      h += concat(o) W_o          (adapters on W_qa, W_qb, W_kva, W_kvb, W_o)
      x = RMSNorm(h);  the first layers: h += SwiGLU_dense(x);  else
      p = softmax(x W_r);  the `groups_kept` of `groups` runs of experts
          with the largest max p;  the top_k largest p of those
      h += Shared(x) + sum over the chosen experts HELD HERE of
           routed_scale x p_e x Expert_e(x)    (not renormalised; ops/moe.py)
    logits = RMSNorm(h) W_head over the held rows of the vocabulary

The attention core (the `softmax(...) v` line) is ops/attention.py's, with
a score width (nope + rope = 192) that is not the value width (128) and no
head shared: at the published size ONE fused, blocked kernel a call, the
scores in VMEM; at the tiny preset the `einsum` form. `attention_plan`
says which, from the shapes alone. No array holds `k`: the core takes
`k_nope` [W, heads, T, 128] and the ONE turned `k_r` [W, 1, T, 64] as two
operands and contracts q's 192 as 128 + 64 (PR 37; until then `k_r` was
broadcast to 128 heads and concatenated, and q sliced, turned and
concatenated, each in float32 over `[128, 1,024, 192]`: 445 ms of a 2,948
ms round, PERF.md section 6).

The trainable tree is {"layers": [{"kva", "kvb", "o", "qa", "qb"}: B [r,
out]]}; the frozen tree holds everything else in `dtype`.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.ops import attention, moe, rotary

# scopes inside `round_grad` a device trace is read by (the model's own
# vocabulary, as models/laguna.SCOPES is Laguna's; docs/OBSERVABILITY.md).
# `mla_proj`: norms, the four compressions, rotary, adapters, W_o;
# `mla_core`: the `softmax(...) v` call alone, whichever side of
# ops/attention.py's dispatch runs; `peer_walk`: the loop of
# `lm.peer_at_a_time` itself (slices, stacked results and residuals)
SCOPES = ("lm_embed", "mla_proj", "mla_core", "lm_router", "lm_experts",
          "lm_dense", "lm_head_loss", "peer_clip", "peer_walk")
# what `mla_proj` is made of, each opened INSIDE it and read under SCOPES +
# SUBSCOPES (under SCOPES alone an instruction's last token is still
# `mla_proj`, and `mla_proj_ms.device` reads what it read): the three
# norms; the four adapted products into the core; the rotation of q's 64
# and of the one shared key, with the cast to the base's type and q's
# head-major write (ops/rotary.py: `rotary_to_heads`, `rotary_from_heads` in
# a trace); `[k_nope | v]`'s cast, head-major transposes and two slices and
# the result's transpose; `W_o` with its adapter. The
# names are models/laguna.py's, none part of another or a frozen leaf's
SUBSCOPES = ("attn_norms", "attn_in", "attn_rotary", "attn_layout",
             "attn_out")
ADAPTED = ("kva", "kvb", "o", "qa", "qb")


@dataclass(frozen=True)
class DeepSeekV2Config:
    hidden: int
    heads: int
    q_rank: int                     # q_lora_rank
    kv_rank: int                    # kv_lora_rank
    nope: int                       # qk_nope_head_dim
    rope: int                       # qk_rope_head_dim
    v_dim: int                      # v_head_dim
    layers: int
    dense_layers: Tuple[int, ...]   # first_k_dense_replace of them
    dense_width: int
    expert_width: int
    shared_experts: int             # one SwiGLU of shared x expert_width
    num_experts: int                # the router's width (published)
    experts_held: int               # experts first_expert .. + held, here
    top_k: int
    groups: int                     # n_group
    groups_kept: int                # topk_group
    routed_scale: float
    norm_topk: bool
    vocab: int                      # rows of the vocabulary held here
    rope_scaling: dict = field(hash=False, compare=False, default=None)
    rope_theta: float = 10000.0
    first_expert: int = 0
    eps: float = 1e-6
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"


ROPE_SCALING = {"factor": 40.0, "original_max_position_embeddings": 4096,
                "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
                "mscale_all_dim": 0.707}

PRESETS = {
    # the published widths; layers 0-4 (the leading dense one and four of
    # the 59 that follow), 40 of the 160 experts and a quarter of the
    # vocabulary: one chip's share when four chips share each layer
    "deepseek_v2_fedlora": DeepSeekV2Config(
        hidden=5120, heads=128, q_rank=1536, kv_rank=512, nope=128, rope=64,
        v_dim=128, layers=5, dense_layers=(0,), dense_width=12288,
        expert_width=1536, shared_experts=2, num_experts=160,
        experts_held=40, top_k=6, groups=8, groups_kept=3,
        routed_scale=16.0, norm_topk=False, vocab=25600,
        rope_scaling=ROPE_SCALING),
    # every mechanism at the CPU tests' size: a score width (6) unlike the
    # value width (4), a shared rotary key, inner norms, 2 of 4 groups
    # kept, unnormalised coefficients, 4 of 16 experts held, float32
    "deepseek_v2_tiny": DeepSeekV2Config(
        hidden=32, heads=4, q_rank=12, kv_rank=8, nope=4, rope=2, v_dim=4,
        layers=3, dense_layers=(0,), dense_width=48, expert_width=8,
        shared_experts=2, num_experts=16, experts_held=4, top_k=3, groups=4,
        groups_kept=2, routed_scale=16.0, norm_topk=False, vocab=64,
        rope_scaling=dict(ROPE_SCALING, original_max_position_embeddings=8,
                          factor=4.0),
        rank=2, alpha=4.0, dtype="float32"),
}


# ------------------------------------------------------------------ rotary


def mscale(factor: float, m: float) -> float:
    """YaRN's attention factor: 0.1 m ln(factor) + 1 (1 for no scaling)."""
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def softmax_scale(cfg: DeepSeekV2Config) -> float:
    """s = (nope + rope)^-0.5 x mscale(factor, mscale_all_dim)^2."""
    scaling = cfg.rope_scaling
    return ((cfg.nope + cfg.rope) ** -0.5
            * mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2)


def rotary_tables(cfg: DeepSeekV2Config, length: int):
    """(cos, sin) float32[T, rope / 2]: YaRN frequencies, times
    mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    scaling = cfg.rope_scaling
    return lm.yarn_tables(cfg.rope, dict(
        scaling, rope_theta=cfg.rope_theta,
        attention_factor=mscale(scaling["factor"], scaling["mscale"])
        / mscale(scaling["factor"], scaling["mscale_all_dim"])), length)


# ----------------------------------------------------------------- forward


def _attention(cfg, h, frozen, adapters):
    """The latent-attention block on h [P, b, T, H]."""
    p, b, t, _ = h.shape
    n, nope, rope, dv = cfg.heads, cfg.nope, cfg.rope, cfg.v_dim
    lora, dtype = frozen["lora_a"], frozen["w_qb"].dtype
    scope = jax.named_scope

    def proj(x, name, part="attn_in"):
        with scope(part):
            return lm.adapted(cfg, x, frozen["w_" + name], lora[name],
                              adapters[name])

    def norm(x, name):
        with scope("attn_norms"):
            return lm.rms(x, frozen[name], cfg.eps)

    def turned(y, width):
        """y [P, b, T, n x width] with every head's last `rope` dimensions
        turned, in float32, then cast to the base's type, head-major: [W,
        n, T, width]."""
        return rotary.turn(y.reshape(p * b, t, -1), *rotary.tables(
            *rotary_tables(cfg, t), width), dtype)

    with scope("mla_proj"):
        x = norm(h, "attn_norm")
        c_q = norm(proj(x, "qa"), "q_norm")
        q = proj(c_q, "qb")                              # [P, b, T, n x 192]
        latent = proj(x, "kva")                          # [P, b, T, 576]
        with scope("attn_in"):
            c_kv = latent[..., :cfg.kv_rank]
        c_kv = norm(c_kv, "kv_norm")
        kv = proj(c_kv, "kvb")                           # [P, b, T, n x 256]
        with scope("attn_rotary"):
            q = turned(q, nope + rope)[:, :, None]       # no head shared
            k_r = turned(latent[..., cfg.kv_rank:], rope)   # [W, 1, T, 64]
        with scope("attn_layout"):
            kv = kv.astype(dtype).reshape(p * b, t, n, nope + dv)
            kv = kv.transpose(0, 2, 1, 3)                # [W, n, T, 256]
            k, v = kv[..., :nope], kv[..., nope:]
    with scope("mla_core"):
        out = attention.attention(q, k, v, t, softmax_scale(cfg), k_r)
    with scope("mla_proj"):
        with scope("attn_layout"):
            out = out[:, :, 0].transpose(0, 2, 1, 3).reshape(p, b, t, n * dv)
        return proj(out, "o", "attn_out")


def attention_plan(cfg: DeepSeekV2Config, length: int) -> dict:
    """How `_attention`'s core is built on windows of `length`, from the
    shapes alone: `fused` 1 where it is ops/attention.py's kernel (0: the
    `einsum` form), `block_share`, the (query block, key block) pairs of
    the [T, T] scores the kernel visits over all pairs (the `einsum` form:
    1), and `shared_key`, 1 where the core receives a key part ONCE for
    all heads (the turned `k_r`, `rope` wide) beside every head's own.
    Every layer is the same."""
    block = attention.blocks(1, length, cfg.nope + cfg.rope, cfg.dtype,
                             cfg.v_dim, cfg.rope)
    return {"fused": int(bool(block)),
            "block_share": attention.block_share(length, length, *block)
            if block else 1.0,
            "shared_key": 1}


def _mlp(cfg, dense, h, frozen):
    """The MLP block of a layer on h [N, H], `dense` or sparse: (result,
    the dispatch's counts, the router's (experts, probabilities)); the
    last two None on a dense layer."""
    x = lm.rms(h, frozen["mlp_norm"], cfg.eps)
    if dense:
        with jax.named_scope("lm_dense"):
            return lm.swiglu(x, frozen["dense"]), None, None
    with jax.named_scope("lm_router"):
        experts, coef, probs = moe.route(
            x, frozen["router"], cfg.top_k, cfg.routed_scale, cfg.groups,
            cfg.groups_kept, cfg.norm_topk)
        # the groups a token's chosen experts lie in, summed over the tokens
        of = experts // (cfg.num_experts // cfg.groups)
        spanned = jnp.sum(jnp.any(
            of[:, :, None] == jnp.arange(cfg.groups, dtype=jnp.int32),
            axis=1), dtype=jnp.int32)
    with jax.named_scope("lm_dense"):
        shared = lm.swiglu(x, frozen["shared"])
    with jax.named_scope("lm_experts"):
        routed, counts = moe.held_experts(x, experts, coef,
                                          frozen["experts"],
                                          cfg.first_expert, cfg.num_experts)
    counts = dict(counts, groups_spanned=spanned,
                  tokens=jnp.asarray(x.shape[0], jnp.int32))
    return shared + routed, counts, (experts, probs)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_of(cfg, dense, h, frozen, adapters):
    """A layer, `dense` or sparse. Jitted, so that a round traces the two
    kinds of layer and not the five layers: the walked attention (a loop,
    rematerialised and transposed) is slow to trace, and set-up pays it
    once a layer otherwise (`setup_s`; PERF.md section 6, PR 35)."""
    h = h + lm.peer_at_a_time(
        lambda h, adapters: _attention(cfg, h, frozen, adapters), h, adapters)
    out, counts, picks = _mlp(cfg, dense, h.reshape(-1, h.shape[-1]), frozen)
    return h + out.reshape(h.shape), counts, picks


def _layer(cfg, at, h, frozen, adapters):
    return _layer_of(cfg, at in cfg.dense_layers, h, frozen, adapters)


# (h [P, b, T, H], counts, picks) of tokens int32[P, b, T] under adapters
# with a peer axis: lm.decoder's walk over this model's layers
hidden_states = lm.decoder(_layer)


def routing(cfg, params, tokens, frozen):
    """`lm.routing` of this model: experts int32[L, b*T, k] and
    probabilities float32[L, b*T, E_all] of `tokens` int32[b, T]."""
    return lm.routing(hidden_states, cfg, params, tokens, frozen)


# ------------------------------------------------------------------- model


def _widths(cfg: DeepSeekV2Config):
    """{projection: (in, out)} of the five adapted projections."""
    n = cfg.heads
    return {"qa": (cfg.hidden, cfg.q_rank),
            "qb": (cfg.q_rank, n * (cfg.nope + cfg.rope)),
            "kva": (cfg.hidden, cfg.kv_rank + cfg.rope),
            "kvb": (cfg.kv_rank, n * (cfg.nope + cfg.v_dim)),
            "o": (n * cfg.v_dim, cfg.hidden)}


def _shapes(cfg: DeepSeekV2Config):
    """({path: (shape, fan_in)} of the frozen leaves, layer by layer,
    [{name: shape}] of the trained ones)."""
    hdim, r = cfg.hidden, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), 1),
              "head": ((hdim, cfg.vocab), hdim),
              "final_norm": ((hdim,), 0), "layers": []}
    trained = []
    for at in range(cfg.layers):
        layer = {"attn_norm": ((hdim,), 0), "mlp_norm": ((hdim,), 0),
                 "q_norm": ((cfg.q_rank,), 0), "kv_norm": ((cfg.kv_rank,), 0),
                 "lora_a": {}}
        for name, (fan_in, out) in _widths(cfg).items():
            layer["w_" + name] = ((fan_in, out), fan_in)
            layer["lora_a"][name] = ((fan_in, r), fan_in)
        if at in cfg.dense_layers:
            layer["dense"] = lm.swiglu_shapes(hdim, cfg.dense_width)
        else:
            layer["router"] = ((hdim, cfg.num_experts), hdim)
            layer["shared"] = lm.swiglu_shapes(
                hdim, cfg.shared_experts * cfg.expert_width)
            layer["experts"] = lm.swiglu_shapes(hdim, cfg.expert_width,
                                                (cfg.experts_held,))
        frozen["layers"].append(layer)
        trained.append({name: (r, out)
                        for name, (_, out) in _widths(cfg).items()})
    return frozen, trained


def deepseek_v2_model(name: str, cfg: DeepSeekV2Config, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak (a sparse layer's recomputation and backward), with NO term
        for the attention's scores: the kernel holds none in HBM. Read off
        the compiled round's memory analysis at the published size (v5e,
        ahead of time; PERF.md section 6, PR 31): its temporaries were
        2.19 GB at a peer block of 1 and 4.03 GB at 3, so a peer added
        0.92 GB to 1.27 GB that every block pays. The terms that come to
        it within a twentieth (0.881 GB): the heads' float32 arrays of a
        layer (q and k at the scores' width, [k_nope | v]) and their
        cotangents, the logits and theirs. Of the 5.22 GB that the 10.33
        GB base, the stacks and 1.34 GB of deltas and noise leave free of
        the 15.75 GiB the chip's runtime states, three peers take 0.506:
        33 MB more than the half that was `peer_step.BLOCK_SHARE` until
        PR 35, so the cell ran a block of 1 while this said 3. With the
        block's attention walked a peer at a time (`lm.peer_at_a_time`)
        the round of 3 compiles at 4.50 GB of temporaries, 15.00 GB with
        its arguments and code of the 16.91 the chip states; a block of 7
        (7.7 GB of temporaries by the line above) does not fit at all.
        Since PR 37 no float32 k at the scores' width exists (the core
        takes `k_nope` and the one rotary key apart, q is cast as it is
        turned): the round of 3 compiles at 4.29 GB of temporaries and the
        round of 1 at 2.14, so a peer adds 1.08 GB where it added 1.16,
        still MORE than the 0.881 counted here (the walk's stacked results
        and residuals are in it). The count is left as it is: it answers
        3 on the chip, and 3 is what fits."""
        t = batch * length
        per_head = 2 * (cfg.nope + cfg.rope) + cfg.nope + cfg.v_dim
        return 2 * 4 * t * (cfg.heads * per_head + cfg.vocab)

    plan = attention_plan(cfg, length)
    return lm.lm_model(name, cfg, length,
                       (frozen_shapes, {"layers": trained_shapes}),
                       hidden_states, step_bytes,
                       {"attention": plan,
                        "gauges": lm.attention_gauges(plan)})
