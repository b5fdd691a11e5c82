"""Olmo-Hybrid-7B (allenai, `model_type` "olmo_hybrid") as a Biscotti
model: a frozen stage of its hybrid decoder (three gated delta-net layers
to one plain full-attention layer, a dense SwiGLU on every layer), with
rank-r adapters on the delta-net layers' `in_proj_qkvz` and `out_proj` and
on the attention layers' q, k, v and o, whose `B` factors are what the
peers train, commit and aggregate (models/lm.py: the FFA-LoRA form).

Source: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json;
the delta rule is arXiv:2412.06464's, its doubled `beta`
(`linear_allow_neg_eigval`) arXiv:2411.12537's, the block the OLMo 2
family's (arXiv:2501.00656): the norm stands on a sub-block's OUTPUT. What
`config.json` does not itself say is listed in benchmark/configs/
olmo_hybrid_7b_fedlora.json (`assumed`): that order for both kinds of
layer, the fused layout of `W_qkvz` and `W_ba`, norm before gate in the
mixer, chunks of 64, no rotary, the q and k norms over the whole
projection, the laws the frozen leaves are drawn from.

    rms(x, w) = x / sqrt(mean x^2 + eps) * w             (weight w, not 1 + w)
    h0 = E[tokens];  layer l (0-based), `full` where l % 4 == 3:
      h += rms(Mixer_l(h), w_1)      the mixer reads h ITSELF, no norm before
      h += rms(SwiGLU(h), w_2)                            (every layer dense)
    logits = rms(h, w_f) W_head over the whole vocabulary (untied)

    `linear` (G key heads of D, H = G value heads of E, D = 96, E = 192):
      [q | k | v | z] = h W_qkvz, a key head at a time [q_g | k_g | v_g |
        z_g];  [b | a] = h W_ba likewise
      [q | k | v] = silu(causal depthwise conv, no bias, of [q | k | v])
      beta = 2 sigmoid(b)  (in (0, 2): I - beta k k^T has an eigenvalue in
        (-1, 1) along k);  g = -exp(A_log) softplus(a + dt_bias)  (float32)
      q <- l2norm(q) D^-0.5;  k <- l2norm(k)
      S = exp(g_t) S_{t-1};  d = beta_t (v_t - S^T k_t);  S_t = S + k_t d^T;
        o_t = S_t^T q_t, S in R^{D x E}, 0 where the window starts
                                                         (ops/delta_rule.py)
      y = rms(o, w [E]) * silu(z)  a value head (norm, THEN gate)
      out = concat(y) W_out
    `full` (`heads` query heads on as many key/value heads of `head_dim`):
      q = rms(h W_q, w_q [heads x head_dim]);  k = rms(h W_k, w_k) (over the
        WHOLE projection, before the head split);  v = h W_v;  NO rotary
      o_h = softmax(q_h k_h^T / sqrt(head_dim) + causal) v_h
      out = concat(o_h) W_o

The rule is ops/delta_rule.py's: its fused kernel at the published widths,
each head laid in whole lane tiles with zero columns (96 | 192 in 128 |
256: `delta_rule.laid`), five value heads a step of its grid; its
`jax.numpy` chunked form at the tiny preset's (`info["gdn_rule"]` says
which). The attention core is ops/attention.py's at heads of 128 | 128,
one query head a key/value head; `attention_plan` says which side of its
dispatch, from the shapes alone.

The trainable tree is {"layers": [{"out", "qkvz"} or {"k", "o", "q", "v"}:
B [r, out]]}; the frozen tree holds everything else in `dtype`. Inside a
block of peers the attention runs a peer at a time (`lm.peer_at_a_time`),
the delta net the block's windows as one batch (models/qwen3_next.py's
`_layer_of` says why), and only the adapters' `B` carry the peer axis.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.ops import attention, delta_rule

# scopes inside `round_grad` a device trace is read by (the model's own
# vocabulary; docs/OBSERVABILITY.md). `gdn_proj`: `W_qkvz`, `W_ba`,
# `W_out`, their adapters, the norm on the mixer's result and the
# residual; `gdn_conv`: the conv, its silu, the split, beta and g;
# `gdn_rule`: from the l2 norms to o_t; `gdn_gate`: the gated norm;
# `lm_attention`: a full layer's mixer, in the parts `SUBSCOPES` names;
# `lm_dense`: the 16 SwiGLUs with their norms and residuals; `peer_walk`:
# the loop of `lm.peer_at_a_time` itself
SCOPES = ("lm_embed", "gdn_proj", "gdn_conv", "gdn_rule", "gdn_gate",
          "lm_attention", "lm_dense", "lm_head_loss", "peer_clip",
          "peer_walk")
# what `lm_attention` is made of (models/laguna.py's list less the rotary,
# which this model has none of): `attn_norms` the q and k norms over the
# whole projection and the norm on the mixer's result, `attn_core` the
# `attention.attention` call alone, `attn_out` `W_o` and the residual
SUBSCOPES = ("attn_norms", "attn_in", "attn_layout", "attn_core",
             "attn_out")


@dataclass(frozen=True)
class OlmoHybridConfig:
    hidden: int
    layers: int
    period: int                     # layer l is `full` where l % period
    #                                 == period - 1 (`layer_types`)
    heads: int                      # query heads of a full layer
    kv_heads: int
    head_dim: int
    key_heads: int                  # linear_num_key_heads
    value_heads: int                # linear_num_value_heads
    key_dim: int                    # linear_key_head_dim
    value_dim: int                  # linear_value_head_dim
    conv: int                       # linear_conv_kernel_dim
    chunk: int                      # tokens a chunk of the rule (assumed)
    mlp_width: int                  # intermediate_size
    vocab: int
    eps: float = 1e-6
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("full" if at % self.period == self.period - 1
                     else "linear" for at in range(self.layers))


PRESETS = {
    # the published widths; layers 0-15 (four whole periods): the FIRST of
    # two pipeline stages, with the head held beside the embedding so that
    # the round has its loss. Nothing is sliced: the whole vocabulary
    "olmo_hybrid_fedlora": OlmoHybridConfig(
        hidden=3840, layers=16, period=4, heads=30, kv_heads=30,
        head_dim=128, key_heads=30, value_heads=30, key_dim=96,
        value_dim=192, conv=4, chunk=64, mlp_width=11008, vocab=100352),
    # the same pattern at the CPU tests' size: one period, a state of 6 x
    # 12 (1 : 2, as 96 | 192), one value head a key head, four chunks a
    # 16-token window, float32
    "olmo_hybrid_tiny": OlmoHybridConfig(
        hidden=32, layers=4, period=4, heads=4, kv_heads=4, head_dim=8,
        key_heads=3, value_heads=3, key_dim=6, value_dim=12, conv=4,
        chunk=4, mlp_width=48, vocab=64, rank=2, alpha=4.0,
        dtype="float32"),
}


def a_log(key, shape):
    """A law of a frozen leaf: log A, A uniform in (0, 16] (the gated
    delta net's own initialisation, as Qwen3-Next's)."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape,
                                                    jnp.float32)))


# ----------------------------------------------------------------- forward


def _delta_net(cfg, h, frozen, adapters):
    """The gated delta-net mixer on h [P, b, T, H], the norm on its result
    included."""
    p, b, t, _ = h.shape
    g, r = cfg.key_heads, cfg.value_heads // cfg.key_heads
    dk, dv = cfg.key_dim, cfg.value_dim
    lora, dtype = frozen["lora_a"], frozen["w_qkvz"].dtype
    scope = jax.named_scope
    with scope("gdn_proj"):
        mixed = lm.adapted(cfg, h, frozen["w_qkvz"], lora["qkvz"],
                           adapters["qkvz"])
        ba = lm.mm(h, frozen["w_ba"])
    with scope("gdn_conv"):
        # a key head at a time [q | k | v | z], [b | a]
        mixed = mixed.reshape(p * b, t, g, 2 * dk + 2 * r * dv)
        ba = ba.reshape(p * b, t, g, 2 * r)
        z = mixed[..., 2 * dk + r * dv:].reshape(p * b, t, g * r, dv)
        qkv = jnp.concatenate(
            [mixed[..., :dk].reshape(p * b, t, g * dk),
             mixed[..., dk:2 * dk].reshape(p * b, t, g * dk),
             mixed[..., 2 * dk:2 * dk + r * dv].reshape(p * b, t, g * r * dv)],
            axis=-1)
        qkv = jax.nn.silu(lm.causal_conv(qkv, frozen["conv_w"]))
        q = qkv[..., :g * dk].reshape(p * b, t, g, dk)
        k = qkv[..., g * dk:2 * g * dk].reshape(p * b, t, g, dk)
        v = qkv[..., 2 * g * dk:].reshape(p * b, t, g * r, dv)
        # `linear_allow_neg_eigval`: the sigmoid doubled
        beta = 2.0 * jax.nn.sigmoid(ba[..., :r].reshape(p * b, t, g * r))
        decay = -jnp.exp(frozen["a_log"].astype(jnp.float32)) \
            * jax.nn.softplus(ba[..., r:].reshape(p * b, t, g * r)
                              + frozen["dt_bias"].astype(jnp.float32))
    with scope("gdn_rule"):
        q = delta_rule.l2norm(q, 1e-6) * dk ** -0.5
        k = delta_rule.l2norm(k, 1e-6)
        out = delta_rule.rule(q.astype(dtype), k.astype(dtype),
                              v.astype(dtype), decay, beta, cfg.chunk)
    with scope("gdn_gate"):
        out = lm.gated_norm(out, z, frozen["gate_norm"], cfg.eps,
                            gate_first=False)
    with scope("gdn_proj"):
        out = lm.adapted(cfg, out.reshape(p, b, t, g * r * dv),
                         frozen["w_out"], lora["out"], adapters["out"])
        return lm.rms(out, frozen["norm"], cfg.eps)


def _attention(cfg, h, frozen, adapters):
    """The full-attention mixer on h [P, b, T, H], the norm on its result
    included: no rotary, q and k normalised over the whole projection."""
    p, b, t, _ = h.shape
    n, kv, dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    lora, dtype = frozen["lora_a"], frozen["wq"].dtype
    scope = jax.named_scope

    def proj(name):
        with scope("attn_in"):
            return lm.adapted(cfg, h, frozen["w" + name], lora[name],
                              adapters[name])

    def heads(y, count, weight=None):
        """[W, count, T, dh], normed over all heads where it has a weight."""
        if weight is not None:
            with scope("attn_norms"):
                y = lm.rms(y, weight, cfg.eps)
        with scope("attn_layout"):
            return y.reshape(p * b, t, count, dh).transpose(0, 2, 1, 3)

    with scope("lm_attention"):
        q = heads(proj("q"), n, frozen["q_norm"])
        k = heads(proj("k"), kv, frozen["k_norm"])
        v = heads(proj("v"), kv)
        with scope("attn_layout"):
            q = q.reshape(p * b, kv, n // kv, t, dh).astype(dtype)
            k, v = k.astype(dtype), v.astype(dtype)
        with scope("attn_core"):
            out = attention.attention(q, k, v, t)
        with scope("attn_layout"):                   # [W, T, n, dh]
            out = out.reshape(p * b, n, t, dh).transpose(0, 2, 1, 3)
        with scope("attn_out"):
            out = lm.adapted(cfg, out.reshape(p, b, t, n * dh),
                             frozen["wo"], lora["o"], adapters["o"])
        with scope("attn_norms"):
            return lm.rms(out, frozen["norm"], cfg.eps)


def attention_plan(cfg: OlmoHybridConfig, length: int) -> dict:
    """How the full layers' core is built on windows of `length`, from the
    shapes alone: `fused` 1 where it is ops/attention.py's kernel (0: the
    `einsum` form), `block_share` the (query block, key block) pairs of
    the [T, T] scores it visits over all pairs (the `einsum` form: 1).
    Every full layer is the same."""
    block = attention.blocks(cfg.heads // cfg.kv_heads, length, cfg.head_dim,
                             cfg.dtype)
    return {"fused": int(bool(block)),
            "block_share": attention.block_share(length, length, *block)
            if block else 1.0}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_of(cfg, kind, h, frozen, adapters):
    """A layer of `kind`. Jitted, so that a round traces the two kinds of
    layer and not the sixteen layers (models/laguna.py:_layer_as). Inside
    a block of peers the attention runs a peer at a time, the delta net
    the block's windows as ONE batch (models/qwen3_next.py:_layer_of)."""
    if kind == "linear":
        mixed = _delta_net(cfg, h, frozen, adapters)
    else:
        mixed = lm.peer_at_a_time(
            lambda h, adapters: _attention(cfg, h, frozen, adapters), h,
            adapters)
    # the residual is the mixer's too
    with jax.named_scope("gdn_proj" if kind == "linear" else "lm_attention"):
        h = h + mixed
    with jax.named_scope("lm_dense"):
        return h + lm.rms(lm.swiglu(h, frozen["mlp"]), frozen["mlp_norm"],
                          cfg.eps), None, None


def _layer(cfg, at, h, frozen, adapters):
    return _layer_of(cfg, cfg.layer_types[at], h, frozen, adapters)


# (h [P, b, T, H], {}, {}) of tokens int32[P, b, T] under adapters with a
# peer axis: lm.decoder's walk over this model's layers (no layer counts or
# picks anything: there is no router)
hidden_states = lm.decoder(_layer)


# ------------------------------------------------------------------- model


def _widths(cfg: OlmoHybridConfig, kind: str):
    """{projection: (in, out)} of a layer's adapted projections."""
    if kind == "linear":
        keys = cfg.key_heads * cfg.key_dim
        values = cfg.value_heads * cfg.value_dim
        return {"qkvz": (cfg.hidden, 2 * keys + 2 * values),
                "out": (values, cfg.hidden)}
    n, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return {"q": (cfg.hidden, n), "k": (cfg.hidden, kv),
            "v": (cfg.hidden, kv), "o": (n, cfg.hidden)}


def _shapes(cfg: OlmoHybridConfig):
    """({path: (shape, fan_in or law)} of the frozen leaves, layer by
    layer, [{name: shape}] of the trained ones). `norm` is the weight of
    the norm on the mixer's result, `mlp_norm` of that on the SwiGLU's."""
    hdim, r = cfg.hidden, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), 1),
              "head": ((hdim, cfg.vocab), hdim),
              "final_norm": ((hdim,), 0), "layers": []}
    trained = []
    for kind in cfg.layer_types:
        widths = _widths(cfg, kind)
        layer = {"norm": ((hdim,), 0), "mlp_norm": ((hdim,), 0),
                 "mlp": lm.swiglu_shapes(hdim, cfg.mlp_width),
                 "lora_a": {name: ((fan_in, r), fan_in)
                            for name, (fan_in, _) in widths.items()}}
        if kind == "linear":
            channels = 2 * cfg.key_heads * cfg.key_dim \
                + cfg.value_heads * cfg.value_dim
            layer.update(
                w_qkvz=(widths["qkvz"], hdim),
                w_ba=((hdim, 2 * cfg.value_heads), hdim),
                conv_w=((cfg.conv, channels), cfg.conv),
                a_log=((cfg.value_heads,), a_log),
                # Mamba-2's law of the step, as Qwen3-Next's preset draws
                # it and for its reason: at ones a head forgets within a
                # token (benchmark/configs/olmo_hybrid_7b_fedlora.json,
                # `assumed`)
                dt_bias=((cfg.value_heads,), lm.step_bias),
                gate_norm=((cfg.value_dim,), 0),
                w_out=(widths["out"], widths["out"][0]))
        else:
            layer.update({"w" + name: (shape, shape[0])
                          for name, shape in widths.items()})
            layer.update(q_norm=((widths["q"][1],), 0),
                         k_norm=((widths["k"][1],), 0))
        frozen["layers"].append(layer)
        trained.append({name: (r, out) for name, (_, out) in widths.items()})
    return frozen, trained


def olmo_hybrid_model(name: str, cfg: OlmoHybridConfig, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)
    chunks = delta_rule.chunks(length, cfg.chunk)  # whole chunks, or refused
    rule = delta_rule.plan(cfg.key_heads, length, cfg.key_dim,
                           cfg.value_dim, cfg.chunk, cfg.dtype,
                           heads=cfg.value_heads)

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak. Read off the compiled round's memory analysis at the
        published size (v5e, ahead of time; PERF.md section 6, PR 48): its
        temporaries are 3.83 GB at a peer block of 1 and 7.01 GB at 3, so
        a peer adds 1.59 GB to 2.25 GB that every block pays (1.31 GB of
        them the deltas and the noise of 21 peers). The terms that come to
        it within a hundredth (1.58 GB), all float32, are the head's: the
        logits over the WHOLE vocabulary, their log-softmax and their
        cotangent (1.23 GB: the widest term, as Granite's, and what holds
        the block at 1); every layer's input, kept for its recomputation;
        six arrays of the hidden width around the final norm. No layer's
        own backward comes near it: the rule's chunks' entry states are
        63 MB a window ([16, 30, 128, 256], the zero columns included).
        With 8.21 GB of base and 1.33 GB of deltas, noise and stacks
        standing, three such peers are 0.642 of what the chip's 15.75 GiB
        have left, over `peer_step.BLOCK_SHARE`: the round walks one at a
        time (a tenth more free memory and it would walk three)."""
        t = batch * length
        return 4 * t * (3 * cfg.vocab + (cfg.layers + 6) * cfg.hidden)

    plan = attention_plan(cfg, length)
    return lm.lm_model(name, cfg, length,
                       (frozen_shapes, {"layers": trained_shapes}),
                       hidden_states, step_bytes,
                       {"attention": plan,
                        "gdn_chunks": chunks,
                        "gdn_rule": rule,
                        "gauges": lm.attention_gauges(plan) + [
                            ("biscotti_gdn_chunks",
                             "chunks a window's gated delta rule is walked "
                             "in (ops/delta_rule.py; static: the window "
                             "over the model's chunk size)", chunks, {}),
                            ("biscotti_gdn_rule_kernel",
                             "1 where the round's gated delta rule is "
                             "ops/delta_rule.py's fused kernel (a chunk's "
                             "system, its solve and the carried state in "
                             "the chip's own memory), 0 the jax.numpy form",
                             rule["kernel"], {}),
                            ("biscotti_gdn_value_heads_a_step",
                             "value heads a step of the rule's kernel "
                             "holds, their solves in step with each other "
                             "(static, delta_rule.plan; 0 off the kernel)",
                             rule["value_heads_a_step"], {}),
                            ("biscotti_gdn_padded_share",
                             "share of the rule's kernel's state products "
                             "that multiply the zero columns its heads "
                             "are laid in whole lane tiles with (static, "
                             "delta_rule.plan; 0 where nothing is padded)",
                             rule["padded_share"], {})]})
