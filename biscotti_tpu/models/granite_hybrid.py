"""Granite-4.0-H-Micro (ibm-granite, `model_type` "granitemoehybrid") as a
Biscotti model: the WHOLE 3.2 B-parameter Mamba-2 / attention hybrid held
frozen, with rank-r adapters on the state-space layers' `in_proj` and
`out_proj` and on the attention layers' q, k, v and o, whose `B` factors
are what the peers train, commit and aggregate (models/lm.py: the FFA-LoRA
form).

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json;
every equation below is in that file's keys (`transformers`'
modeling_granitemoehybrid.py is the statement of each). What it does not
state is listed in benchmark/configs/granite_4.0_h_micro_fedlora.json
(`assumed`): the laws the frozen leaves are drawn from, `time_step_limit`
(0, inf).

    h0 = embedding_multiplier x E[tokens]
    layer l, of kind layer_types[l] (pre-norm, rms_norm_eps 1e-5):
      h += residual_multiplier x Mixer_l(RMSNorm(h))
      h += residual_multiplier x SwiGLU(RMSNorm(h))   (one dense MLP a layer)
    logits = RMSNorm(h) E^T / logits_scaling          (the head is E: tied)

    Mixer "attention": q, k, v = x Wq, x Wk, x Wv; `heads` query heads on
      `kv_heads` key/value heads; NO rotary ("nope");
      o = softmax(attention_multiplier x q k^T + causal) v;  out = o Wo
    Mixer "mamba" (Mamba-2; d_inner = ssm_heads x ssm_head_dim, one group):
      [z | xBC | dt] = x W_in                  (d_inner | d_inner + 2 N | heads)
      xBC = silu(causal depthwise conv(xBC) + bias);  [x | B | C] = xBC
      dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t, S = 0 where the window
        starts;  y_t = S_t C_t + D_h x_t       (ops/ssm.py, chunks of `chunk`)
      u = y * silu(z);  y' = w * u / sqrt(mean(u^2) + eps)   (gate, THEN norm)
      out = y' W_out

The recurrence is ops/ssm.py's chunked scan; the attention core is
ops/attention.py's (heads of 64 | 64, four query heads a key/value head);
`attention_plan` says which side of its dispatch, from the shapes alone.

The trainable tree is {"layers": [{"in", "out"} or {"k", "o", "q", "v"}: B
[r, out]]}; the frozen tree holds everything else in `dtype`, the
embedding ONCE (`lm.logits` reads it again as the head). Windows never
meet: the batch axis of the scan, the conv and the attention is [P x b],
and only the adapters' `B` carry the peer axis.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.ops import attention, ssm

# scopes inside `round_grad` a device trace is read by (the model's own
# vocabulary, as models/laguna.SCOPES is Laguna's; docs/OBSERVABILITY.md).
# `ssm_proj`: the block norm, `in_proj`, `out_proj`, their adapters and the
# residual; `ssm_conv`: the conv, its silu and the split; `ssm_scan`: from
# the step's softplus to y_t, the D term included; `ssm_gate`: the gated
# norm; `lm_attention`: an attention layer's mixer whole; `lm_dense`: the
# 40 MLPs with their norms and residuals
SCOPES = ("lm_embed", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate",
          "lm_attention", "lm_dense", "lm_head_loss", "peer_clip")


@dataclass(frozen=True)
class GraniteHybridConfig:
    hidden: int
    layer_types: Tuple[str, ...]    # "mamba" | "attention"
    heads: int                      # query heads of an attention layer
    kv_heads: int
    head_dim: int
    mlp_width: int                  # shared_intermediate_size
    ssm_heads: int                  # mamba_n_heads
    ssm_head_dim: int               # mamba_d_head
    ssm_state: int                  # mamba_d_state
    conv: int                       # mamba_d_conv
    chunk: int                      # mamba_chunk_size
    vocab: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    eps: float = 1e-5
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    @property
    def inner(self) -> int:
        """d_inner = mamba_expand x hidden = ssm_heads x ssm_head_dim."""
        return self.ssm_heads * self.ssm_head_dim


PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

PRESETS = {
    # as published, uncut: all 40 layers (four periods mmmmmAmmmm), the
    # whole vocabulary
    "granite_h_micro_fedlora": GraniteHybridConfig(
        hidden=2048, layer_types=PERIOD * 4, heads=32, kv_heads=8,
        head_dim=64, mlp_width=8192, ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, conv=4, chunk=256, vocab=100352,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.015625, logits_scaling=8.0),
    # both kinds of layer in the period's order at the CPU tests' size:
    # four chunks a 16-token window, a head group of 2, float32
    "granite_h_tiny": GraniteHybridConfig(
        hidden=32, layer_types=("mamba", "mamba", "attention", "mamba"),
        heads=4, kv_heads=2, head_dim=8, mlp_width=48, ssm_heads=4,
        ssm_head_dim=16, ssm_state=8, conv=4, chunk=4, vocab=64,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        attention_multiplier=0.1, logits_scaling=8.0, rank=2, alpha=4.0,
        dtype="float32"),
}


# ---------------------------------------------------- the frozen leaves' laws


def a_log(key, shape):
    """log A, A uniform in [1, 16] (Mamba-2's own initialisation)."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


dt_bias = lm.step_bias  # Mamba-2's own law of the step (models/lm.py)


# ----------------------------------------------------------------- forward


# the conv (with its bias) and the gated norm (the gate BEFORE the norm, the
# mean over all of d_inner: one group) are models/lm.py's, which the other
# hybrid shares
causal_conv, gated_norm = lm.causal_conv, lm.gated_norm


def _mamba(cfg, h, frozen, adapters):
    """The state-space mixer on h [P, b, T, H]."""
    p, b, t, _ = h.shape
    inner, n, heads = cfg.inner, cfg.ssm_state, cfg.ssm_heads
    lora, dtype = frozen["lora_a"], frozen["w_in"].dtype
    with jax.named_scope("ssm_proj"):
        x = lm.rms(h, frozen["norm"], cfg.eps)
        mixed = lm.adapted(cfg, x, frozen["w_in"], lora["in"],
                           adapters["in"]).reshape(p * b, t, -1)
        z, xbc, dt = (mixed[..., :inner], mixed[..., inner:-heads],
                      mixed[..., -heads:])
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(causal_conv(xbc, frozen["conv_w"],
                                      frozen["conv_b"]))
        x = xbc[..., :inner].reshape(p * b, t, heads, cfg.ssm_head_dim)
        b_t, c_t = xbc[..., inner:inner + n], xbc[..., inner + n:]
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt + frozen["dt_bias"].astype(jnp.float32))
        y = ssm.scan(x.astype(dtype), dt,
                     -jnp.exp(frozen["a_log"].astype(jnp.float32)),
                     b_t.astype(dtype), c_t.astype(dtype),
                     frozen["d"].astype(jnp.float32), cfg.chunk)
    with jax.named_scope("ssm_gate"):
        out = gated_norm(y.reshape(p * b, t, inner), z, frozen["gate_norm"],
                         cfg.eps)
    with jax.named_scope("ssm_proj"):
        return h + cfg.residual_multiplier * lm.adapted(
            cfg, out.reshape(p, b, t, inner), frozen["w_out"], lora["out"],
            adapters["out"])


def _attention(cfg, h, frozen, adapters):
    """The attention mixer on h [P, b, T, H]: no rotary, the scores times
    `attention_multiplier`."""
    p, b, t, _ = h.shape
    n, kv, dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    lora, dtype = frozen["lora_a"], frozen["wq"].dtype
    x = lm.rms(h, frozen["norm"], cfg.eps)

    def heads(name, count):
        y = lm.adapted(cfg, x, frozen["w" + name], lora[name], adapters[name])
        return y.reshape(p * b, t, count, dh).transpose(0, 2, 1, 3)

    q = heads("q", n).reshape(p * b, kv, n // kv, t, dh).astype(dtype)
    out = attention.attention(q, heads("k", kv).astype(dtype),
                              heads("v", kv).astype(dtype), t,
                              cfg.attention_multiplier)
    out = out.reshape(p * b, n, t, dh).transpose(0, 2, 1, 3)
    return h + cfg.residual_multiplier * lm.adapted(
        cfg, out.reshape(p, b, t, n * dh), frozen["wo"], lora["o"],
        adapters["o"])


def attention_plan(cfg: GraniteHybridConfig, length: int) -> dict:
    """How the attention layers' core is built on windows of `length`, from
    the shapes alone: `fused` 1 where it is ops/attention.py's kernel (0:
    the `einsum` form), `block_share` the (query block, key block) pairs of
    the [T, T] scores it visits over all pairs (the `einsum` form: 1).
    Every attention layer is the same."""
    block = attention.blocks(cfg.heads // cfg.kv_heads, length, cfg.head_dim,
                             cfg.dtype)
    return {"fused": int(bool(block)),
            "block_share": attention.block_share(length, length, *block)
            if block else 1.0}


def _layer(cfg, at, h, frozen, adapters):
    if cfg.layer_types[at] == "mamba":
        h = _mamba(cfg, h, frozen, adapters)
    else:
        with jax.named_scope("lm_attention"):
            h = _attention(cfg, h, frozen, adapters)
    with jax.named_scope("lm_dense"):
        x = lm.rms(h, frozen["mlp_norm"], cfg.eps)
        h = h + cfg.residual_multiplier * lm.swiglu(x, frozen["mlp"])
    return h, None, None


# (h [P, b, T, H], {}, {}) of tokens int32[P, b, T] under adapters with a
# peer axis: lm.decoder's walk over this model's layers (no layer counts or
# picks anything: there is no router)
hidden_states = lm.decoder(_layer)


# ------------------------------------------------------------------- model


def _widths(cfg: GraniteHybridConfig, kind: str):
    """{projection: (in, out)} of a layer's adapted projections."""
    if kind == "mamba":
        return {"in": (cfg.hidden, 2 * cfg.inner + 2 * cfg.ssm_state
                       + cfg.ssm_heads),
                "out": (cfg.inner, cfg.hidden)}
    n, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return {"q": (cfg.hidden, n), "k": (cfg.hidden, kv),
            "v": (cfg.hidden, kv), "o": (n, cfg.hidden)}


def _shapes(cfg: GraniteHybridConfig):
    """({path: (shape, fan_in or law)} of the frozen leaves, layer by
    layer, [{name: shape}] of the trained ones). The embedding is drawn
    fan-in scaled as the head it also is."""
    hdim, r = cfg.hidden, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), hdim),
              "final_norm": ((hdim,), 0), "layers": []}
    trained = []
    for kind in cfg.layer_types:
        widths = _widths(cfg, kind)
        layer = {"norm": ((hdim,), 0), "mlp_norm": ((hdim,), 0),
                 "mlp": lm.swiglu_shapes(hdim, cfg.mlp_width),
                 "lora_a": {name: ((fan_in, r), fan_in)
                            for name, (fan_in, _) in widths.items()}}
        if kind == "mamba":
            channels = cfg.inner + 2 * cfg.ssm_state
            layer.update(
                w_in=(widths["in"], hdim), w_out=(widths["out"], cfg.inner),
                conv_w=((cfg.conv, channels), cfg.conv),
                conv_b=((channels,), cfg.conv),
                dt_bias=((cfg.ssm_heads,), dt_bias),
                a_log=((cfg.ssm_heads,), a_log), d=((cfg.ssm_heads,), 0),
                gate_norm=((cfg.inner,), 0))
        else:
            # q and k so that the scores have unit variance at the draw,
            # as 1 / sqrt(d) attention has under fan-in scaled weights:
            # `attention_multiplier` is 1 / d (a trained model's q and k
            # align), and fan-in scaled q, k would make every softmax
            # uniform within 0.125, a mean over the tokens before
            sharp = cfg.attention_multiplier * math.sqrt(cfg.head_dim)
            layer.update({"w" + name: (shape, shape[0] * (
                sharp if name in ("q", "k") else 1))
                for name, shape in widths.items()})
        frozen["layers"].append(layer)
        trained.append({name: (r, out) for name, (_, out) in widths.items()})
    return frozen, trained


def granite_hybrid_model(name: str, cfg: GraniteHybridConfig, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)
    chunks = ssm.chunks(length, cfg.chunk)  # whole chunks, or refused

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak. Read off the compiled round's memory analysis at the
        published size (v5e, ahead of time; PERF.md section 6, PR 33): its
        temporaries are 3.26 GB at a peer block of 1 and 7.43 GB at 3, so
        a peer adds 2.08 GB to 1.18 GB that every block pays. The terms
        that come to it within a fiftieth (2.05 GB), all float32: the
        logits over the WHOLE vocabulary, their log-softmax and their
        cotangent (1.23 GB: the widest term, and what holds the block at
        1); every layer's input, kept for its recomputation; a layer's
        scan at its backward, four arrays [chunks, heads, chunk, chunk];
        six arrays of `in_proj`'s width. With 6.39 GB of base and 1.67 GB
        of deltas and noise standing, three such peers are 0.695 of what
        the chip's 15.75 GiB have left, over `peer_step.BLOCK_SHARE`: the
        round walks one at a time."""
        t = batch * length
        wide = 2 * cfg.inner + 2 * cfg.ssm_state + cfg.ssm_heads
        return 4 * t * (3 * cfg.vocab + cfg.layers * cfg.hidden
                        + 4 * cfg.ssm_heads * min(cfg.chunk, length)
                        + 6 * wide)

    plan = attention_plan(cfg, length)
    return lm.lm_model(name, cfg, length,
                       (frozen_shapes, {"layers": trained_shapes}),
                       hidden_states, step_bytes,
                       {"attention": plan,
                        "ssm_chunks": chunks,
                        "gauges": lm.attention_gauges(plan) + [
                            ("biscotti_ssm_chunks",
                             "chunks a window's state-space scan is walked "
                             "in (ops/ssm.py; static: the window over the "
                             "model's chunk size)", chunks, {})]})
