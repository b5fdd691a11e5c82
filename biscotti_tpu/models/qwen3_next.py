"""Qwen3-Next-80B-A3B-Instruct (Qwen, `model_type` "qwen3_next") as a
Biscotti model: a frozen share of its hybrid decoder (three gated
delta-net layers to one gated softmax-attention layer, a sparse MLP of 512
experts on every layer), with rank-r adapters on the delta-net layers'
`in_proj_qkvz` and `out_proj` and on the attention layers' q, k, v and o,
whose `B` factors are what the peers train, commit and aggregate
(models/lm.py: the FFA-LoRA form).

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json;
the delta rule is arXiv:2412.06464's, `transformers`'
modeling_qwen3_next.py the statement of each layer. What `config.json` does
not itself say is listed in benchmark/configs/
qwen3_next_80b_a3b_fedlora.json (`assumed`): the norms read as `1 + w`,
the two l2 norms and the 128^-0.5, the output gate taken from `q_proj`'s
second half of each head, the shared expert's sigmoid gate, norm before
gate in the mixer, no multi-token-prediction head, the laws the frozen
leaves are drawn from.

    rms0(x, w) = x / sqrt(mean x^2 + eps) * (1 + w)        (zero-centred)
    h0 = E[tokens];  layer l (0-based):
      h += Mixer_l(rms0(h));  Mixer_l gated attention where (l + 1) %
           full_attention_interval == 0, else the gated delta net
      h += MoE(rms0(h))                                    (every layer)
    logits = rms0(h) W_head over the held rows of the vocabulary (untied)

    gated delta net (G key heads of D, H = 2 G value heads of E):
      [q | k | v | z] = x W_qkvz, a key head at a time [q_g | k_g | v_2g,
        v_2g+1 | z_2g, z_2g+1];  [b | a] = x W_ba likewise
      [q | k | v] = silu(causal depthwise conv, no bias, of [q | k | v])
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   (float32)
      q <- l2norm(q) D^-0.5;  k <- l2norm(k)
      S = exp(g_t) S_{t-1};  d = beta_t (v_t - S^T k_t);  S_t = S + k_t d^T;
        o_t = S_t^T q_t, S = 0 where the window starts     (ops/delta_rule.py)
      y = rms(o, w [E]) * silu(z)  a value head (norm, THEN gate; weight w)
      out = concat(y) W_out
    gated attention (`heads` query heads on `kv_heads` of `head_dim`):
      [q | gate] = x W_q a head;  k = x W_k;  v = x W_v
      q_h <- rms0(q_h, w_qn);  k_j <- rms0(k_j, w_kn)
      rotate-half rotary on the first rotary_factor x head_dim, theta 1e7
      o_h = softmax(q_h k_j^T / sqrt(head_dim) + causal) v_j
      out = concat(o_h * sigmoid(gate_h)) W_o
    MoE: p = softmax(x W_r) over ALL experts, the top_k largest over their
      sum; sum over those HELD HERE of p_e Expert_e(x)      (ops/moe.py)
      + sigmoid(x w_sg) Shared(x)

The rule is ops/delta_rule.py's: its fused kernel at the published widths
(heads of 128 | 128, chunks of 64), its `jax.numpy` chunked form at the
tiny preset's (`info["gdn_rule"]` says which); the attention core is
ops/attention.py's (heads of 256 | 256, eight query heads a key/value
head); the routed experts are ops/moe.py's, 128 groups of 2,048 x 512 at
the published size. `attention_plan` says which side of the core's
dispatch, from the shapes alone.

The trainable tree is {"layers": [{"out", "qkvz"} or {"k", "o", "q", "v"}:
B [r, out]]}; the frozen tree holds everything else in `dtype`. A block of
peers meets the expert dispatch ONCE a layer (models/laguna.py's module
doc); both mixers, the attention and the delta net, run a peer at a time
inside it (`_layer_of` says how, and what it was measured against), and
only the adapters' `B` carry the peer axis.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from biscotti_tpu.models import lm
from biscotti_tpu.ops import attention, delta_rule, moe

# scopes inside `round_grad` a device trace is read by (the model's own
# vocabulary; docs/OBSERVABILITY.md). `gdn_proj`: the block norm, `W_qkvz`,
# `W_ba`, `W_out`, their adapters and the residual; `gdn_conv`: the conv,
# its silu, the split, beta and g; `gdn_rule`: from the l2 norms to o_t;
# `gdn_gate`: the gated norm; `lm_attention`: an attention layer's mixer,
# in the parts `SUBSCOPES` names; `lm_dense`: the shared expert and its
# gate; `peer_walk`: the loop of `lm.peer_at_a_time` itself
SCOPES = ("lm_embed", "gdn_proj", "gdn_conv", "gdn_rule", "gdn_gate",
          "lm_attention", "lm_router", "lm_experts", "lm_dense",
          "lm_head_loss", "peer_clip", "peer_walk")
# what `lm_attention` is made of (models/laguna.py's list, read under
# SCOPES + SUBSCOPES): `attn_norms` the block norm and the two head norms,
# `attn_core` the `attention.attention` call alone, `attn_out` the output
# gate and `W_o`
SUBSCOPES = ("attn_norms", "attn_in", "attn_rotary", "attn_layout",
             "attn_core", "attn_out")


@dataclass(frozen=True)
class Qwen3NextConfig:
    hidden: int
    layers: int
    full_attention_interval: int
    heads: int                      # query heads of an attention layer
    kv_heads: int
    head_dim: int
    rotary_factor: float            # partial_rotary_factor
    rope_theta: float
    key_heads: int                  # linear_num_key_heads
    value_heads: int                # linear_num_value_heads
    key_dim: int                    # linear_key_head_dim
    value_dim: int                  # linear_value_head_dim
    conv: int                       # linear_conv_kernel_dim
    chunk: int                      # tokens a chunk of the rule (assumed)
    expert_width: int
    shared_width: int
    num_experts: int                # the router's width (published)
    experts_held: int               # experts first_expert .. + held, here
    top_k: int
    vocab: int                      # rows of the vocabulary held here
    first_expert: int = 0
    eps: float = 1e-6
    rank: int = 16
    alpha: float = 32.0
    dtype: str = "bfloat16"
    # the block, head and final norms read their weight as `1 + w` (a
    # constant of the family, no field: `lm.logits` asks the config)
    zero_centred = True

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple("attention" if (at + 1) % self.full_attention_interval
                     == 0 else "gdn" for at in range(self.layers))


PRESETS = {
    # the published widths; layers 0-11 (three whole periods), 128 of the
    # 512 experts and a quarter of the vocabulary: one chip's share when
    # four chips share each layer, on the first of four pipeline stages
    "qwen3_next_fedlora": Qwen3NextConfig(
        hidden=2048, layers=12, full_attention_interval=4, heads=16,
        kv_heads=2, head_dim=256, rotary_factor=0.25, rope_theta=1e7,
        key_heads=16, value_heads=32, key_dim=128, value_dim=128, conv=4,
        chunk=64, expert_width=512, shared_width=512, num_experts=512,
        experts_held=128, top_k=10, vocab=37984),
    # the same pattern at the CPU tests' size: two periods, a head group of
    # 2, two value heads a key head, four chunks a 16-token window, 4 of 16
    # experts held, float32
    "qwen3_next_tiny": Qwen3NextConfig(
        hidden=32, layers=8, full_attention_interval=4, heads=4,
        kv_heads=2, head_dim=8, rotary_factor=0.25, rope_theta=1e7,
        key_heads=2, value_heads=4, key_dim=8, value_dim=8, conv=4,
        chunk=4, expert_width=8, shared_width=8, num_experts=16,
        experts_held=4, top_k=3, vocab=64, rank=2, alpha=4.0,
        dtype="float32"),
}


# ---------------------------------------------------- the frozen leaves' laws


def around_zero(key, shape):
    """A zero-centred norm's weight: 0.1 N(0, 1) (the scale is 1 + w)."""
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def a_log(key, shape):
    """log A, A uniform in (0, 16] (the model class's initialisation)."""
    return jnp.log(16.0 * (1.0 - jax.random.uniform(key, shape,
                                                    jnp.float32)))


# ----------------------------------------------------------------- forward


def _delta_net(cfg, h, frozen, adapters):
    """The gated delta-net mixer on h [P, b, T, H]."""
    p, b, t, _ = h.shape
    g, r = cfg.key_heads, cfg.value_heads // cfg.key_heads
    dk, dv = cfg.key_dim, cfg.value_dim
    lora, dtype = frozen["lora_a"], frozen["w_qkvz"].dtype
    scope = jax.named_scope
    with scope("gdn_proj"):
        x = lm.rms(h, frozen["norm"], cfg.eps, cfg.zero_centred)
        mixed = lm.adapted(cfg, x, frozen["w_qkvz"], lora["qkvz"],
                           adapters["qkvz"])
        ba = lm.mm(x, frozen["w_ba"])
    with scope("gdn_conv"):
        # a key head at a time [q | k | v v | z z], [b b | a a]
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
        beta = jax.nn.sigmoid(ba[..., :r].reshape(p * b, t, g * r))
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
        return lm.adapted(cfg, out.reshape(p, b, t, g * r * dv),
                          frozen["w_out"], lora["out"], adapters["out"])


def rotary_tables(cfg: Qwen3NextConfig, length: int):
    """(cos, sin) float32[T, rot / 2] and the rotated width `rot`."""
    rot = int(cfg.head_dim * cfg.rotary_factor)
    return lm.yarn_tables(rot, {"rope_theta": cfg.rope_theta}, length) \
        + (rot,)


def _attention(cfg, h, frozen, adapters):
    """The gated attention mixer on h [P, b, T, H]."""
    p, b, t, _ = h.shape
    n, kv, dh = cfg.heads, cfg.kv_heads, cfg.head_dim
    lora, dtype = frozen["lora_a"], frozen["wq"].dtype
    scope = jax.named_scope

    def proj(name, count, width):
        with scope("attn_in"):
            y = lm.adapted(cfg, x, frozen["w" + name], lora[name],
                           adapters[name])
        with scope("attn_layout"):
            return y.reshape(p * b, t, count, width)

    def heads(y, weight=None):
        """[W, count, T, dh], each head normed where it has a weight."""
        if weight is not None:
            with scope("attn_norms"):
                y = lm.rms(y, weight, cfg.eps, cfg.zero_centred)
        with scope("attn_layout"):
            return y.transpose(0, 2, 1, 3)

    with scope("lm_attention"):
        with scope("attn_norms"):
            x = lm.rms(h, frozen["norm"], cfg.eps, cfg.zero_centred)
        wide = proj("q", n, 2 * dh)                  # a head [q | gate]
        with scope("attn_layout"):
            q, gate = wide[..., :dh], wide[..., dh:]
        q = heads(q, frozen["q_norm"])
        k = heads(proj("k", kv, dh), frozen["k_norm"])
        v = heads(proj("v", kv, dh))
        with scope("attn_rotary"):
            cos, sin, rot = rotary_tables(cfg, t)
            q = lm.rotate_half(q, cos, sin, rot)
            k = lm.rotate_half(k, cos, sin, rot)
        with scope("attn_layout"):
            q = q.reshape(p * b, kv, n // kv, t, dh).astype(dtype)
            k, v = k.astype(dtype), v.astype(dtype)
        with scope("attn_core"):
            out = attention.attention(q, k, v, t)
        with scope("attn_layout"):                   # [W, T, n, dh]
            out = out.reshape(p * b, n, t, dh).transpose(0, 2, 1, 3)
        with scope("attn_out"):
            out = (out * jax.nn.sigmoid(gate)).reshape(p, b, t, n * dh)
            return lm.adapted(cfg, out, frozen["wo"], lora["o"],
                              adapters["o"])


def attention_plan(cfg: Qwen3NextConfig, length: int) -> dict:
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


def _mlp(cfg, h, frozen):
    """The sparse MLP on h [N, H]: (result, the dispatch's counts, the
    router's (experts, probabilities))."""
    x = lm.rms(h, frozen["mlp_norm"], cfg.eps, cfg.zero_centred)
    with jax.named_scope("lm_router"):
        experts, coef, probs = moe.route(x, frozen["router"], cfg.top_k, 1.0)
    with jax.named_scope("lm_dense"):
        shared = jax.nn.sigmoid(lm.mm(x, frozen["shared_gate"])) \
            * lm.swiglu(x, frozen["shared"])
    with jax.named_scope("lm_experts"):
        routed, counts = moe.held_experts(x, experts, coef,
                                          frozen["experts"],
                                          cfg.first_expert, cfg.num_experts)
    return shared + routed, counts, (experts, probs)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_of(cfg, kind, h, frozen, adapters):
    """A layer of `kind`. Jitted, so that a round traces the two kinds of
    layer and not the twelve layers (models/laguna.py:_layer_as). The
    block of peers is there for the routed experts; inside it BOTH mixers
    run a peer at a time, ONE walk a layer whose body is the whole mixer
    (each of its parts opens its scope inside, so a trace reads the walk
    itself as `peer_walk` and the parts as their own). The delta net's
    dozen float32 passes as wide as `in_proj_qkvz` (conv, silu, split,
    gated norm, the casts around the rule) stay in the chip's fast memory
    on one window and stream from HBM on three: on the v5e `gdn_proj` +
    `gdn_conv` + `gdn_gate` read 839.6 ms a round on the block of 3 and
    407.6 at a block of 1 (PR 39's two sides; PRs 42 and 43 walked them
    and were refused on other grounds: PERF.md section 6, PR 49).
    The attention's walk is `lm.peer_at_a_time`, a `lax.map` whose
    backward pass reads what the forward loop STACKED over the peers (27.6
    ms of `peer_walk` a round); of the delta net's mixer that is 0.29 GB a
    peer and layer, written and read back through HBM, which gave back
    373 of the 597 ms the passes had gained (`peer_walk` and `mixed`: the
    same entry). So the delta net's walk is unrolled: a peer's mixer
    after the other on slices of the block, their results joined once,
    and nothing stacked.
    While the rule was `jax.numpy` a walk LOST (4,047 ms a round walked,
    3,557 not: PR 38); since PR 39 the rule is a kernel. A block of ONE
    peer walks nothing and lowers as before the walk was there, so a
    model whose cell runs a block of 1 (models/olmo_hybrid.py) gains
    nothing from it."""
    if kind == "gdn" and h.shape[0] > 1:
        with jax.named_scope("peer_walk"):
            mixed = jnp.concatenate([
                _delta_net(cfg, h[peer:peer + 1], frozen,
                           jax.tree.map(lambda b: b[peer:peer + 1], adapters))
                for peer in range(h.shape[0])])
    else:
        mixer = _delta_net if kind == "gdn" else _attention
        mixed = lm.peer_at_a_time(
            lambda h, adapters: mixer(cfg, h, frozen, adapters), h, adapters)
    # the residual is the mixer's too
    with jax.named_scope("gdn_proj" if kind == "gdn" else "lm_attention"):
        h = h + mixed
    out, counts, picks = _mlp(cfg, h.reshape(-1, h.shape[-1]), frozen)
    return h + out.reshape(h.shape), counts, picks


def _layer(cfg, at, h, frozen, adapters):
    return _layer_of(cfg, cfg.layer_types[at], h, frozen, adapters)


# (h [P, b, T, H], counts, picks) of tokens int32[P, b, T] under adapters
# with a peer axis: lm.decoder's walk over this model's layers
hidden_states = lm.decoder(_layer)


def routing(cfg, params, tokens, frozen):
    """`lm.routing` of this model: experts int32[L, b*T, k] and
    probabilities float32[L, b*T, E_all] of `tokens` int32[b, T]."""
    return lm.routing(hidden_states, cfg, params, tokens, frozen)


# ------------------------------------------------------------------- model


def _widths(cfg: Qwen3NextConfig, kind: str):
    """{projection: (in, out)} of a layer's adapted projections."""
    if kind == "gdn":
        keys = cfg.key_heads * cfg.key_dim
        values = cfg.value_heads * cfg.value_dim
        return {"qkvz": (cfg.hidden, 2 * keys + 2 * values),
                "out": (values, cfg.hidden)}
    n, kv = cfg.heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return {"q": (cfg.hidden, 2 * n), "k": (cfg.hidden, kv),
            "v": (cfg.hidden, kv), "o": (n, cfg.hidden)}


def _shapes(cfg: Qwen3NextConfig):
    """({path: (shape, fan_in or law)} of the frozen leaves, layer by
    layer, [{name: shape}] of the trained ones)."""
    hdim, r = cfg.hidden, cfg.rank
    frozen = {"embed": ((cfg.vocab, hdim), 1),
              "head": ((hdim, cfg.vocab), hdim),
              "final_norm": ((hdim,), around_zero), "layers": []}
    trained = []
    for kind in cfg.layer_types:
        widths = _widths(cfg, kind)
        layer = {"norm": ((hdim,), around_zero),
                 "mlp_norm": ((hdim,), around_zero),
                 "router": ((hdim, cfg.num_experts), hdim),
                 "shared": lm.swiglu_shapes(hdim, cfg.shared_width),
                 "shared_gate": ((hdim, 1), hdim),
                 "experts": lm.swiglu_shapes(hdim, cfg.expert_width,
                                             (cfg.experts_held,)),
                 "lora_a": {name: ((fan_in, r), fan_in)
                            for name, (fan_in, _) in widths.items()}}
        if kind == "gdn":
            channels = 2 * cfg.key_heads * cfg.key_dim \
                + cfg.value_heads * cfg.value_dim
            layer.update(
                w_qkvz=(widths["qkvz"], hdim),
                w_ba=((hdim, 2 * cfg.value_heads), hdim),
                conv_w=((cfg.conv, channels), cfg.conv),
                a_log=((cfg.value_heads,), a_log),
                # Mamba-2's law of the step, NOT the model class's ones: at
                # dt_bias = 1 a head's decay is exp(-1.3 A) a token and all
                # but the slowest forget within one (benchmark/configs/
                # qwen3_next_80b_a3b_fedlora.json, `assumed`)
                dt_bias=((cfg.value_heads,), lm.step_bias),
                gate_norm=((cfg.value_dim,), 0),
                w_out=(widths["out"], widths["out"][0]))
        else:
            layer.update({"w" + name: (shape, shape[0])
                          for name, shape in widths.items()})
            layer.update(q_norm=((cfg.head_dim,), around_zero),
                         k_norm=((cfg.head_dim,), around_zero))
        frozen["layers"].append(layer)
        trained.append({name: (r, out) for name, (_, out) in widths.items()})
    return frozen, trained


def qwen3_next_model(name: str, cfg: Qwen3NextConfig, length: int):
    """The Biscotti `Model` of `cfg` on windows of `length` tokens."""
    frozen_shapes, trained_shapes = _shapes(cfg)
    chunks = delta_rule.chunks(length, cfg.chunk)  # whole chunks, or refused

    def step_bytes(batch):
        """Bytes one peer's step adds to what a block holds live at its
        peak. Read off the compiled round's memory analysis at the
        published size (v5e, ahead of time; PERF.md section 6, PR 39,
        recounted with the rule a kernel): its temporaries are 2.00 GB at
        a peer block of 1 and 3.98 GB at 3 (2.34 and 4.60 while the rule
        was `jax.numpy`, PR 38), so a peer adds 0.99 GB to 1.01 GB that
        every block pays. The terms that come to it within a thirtieth
        (0.970 GB), all float32: the logits over the held vocabulary,
        their log-softmax and their cotangent (0.47 GB); every layer's
        input, kept for its recomputation; four arrays of `in_proj_qkvz`'s
        width (six before: the rule's head-major copies of q, k and v and
        its `delta` are no arrays any more); a token's `top_k` gathered
        expert rows, forward and backward; a layer's chunks' entry states,
        [chunks, value heads, 128, 128], which the recomputed forward
        hands the backward (their cotangents never leave the chip's own
        memory). With 10.85 GB of base and 0.68 GB of deltas and noise
        standing, three such peers are 0.542 of what the chip's 15.75 GiB
        have left (0.554 by the compiled round's count), inside
        `peer_step.BLOCK_SHARE`: the round walks three at a time (PR 38's
        count read 0.618 and the round walked one). Since PR 49 a block's
        delta net runs a peer at a time and the compiled round's
        temporaries at 3 are 3.66 GB (2.00 at 1, which walks nothing, as
        before): a peer adds 0.83 GB, so this count stands a sixth over
        what the compiler holds; it is kept, the next block the 21 peers
        divide into is 7, and 7 fit by neither count."""
        t = batch * length
        wide = 2 * cfg.key_heads * cfg.key_dim \
            + 2 * cfg.value_heads * cfg.value_dim
        states = chunks * cfg.value_heads * cfg.key_dim * cfg.value_dim
        return 4 * (t * (3 * cfg.vocab + cfg.layers * cfg.hidden + 4 * wide
                         + 2 * cfg.top_k * cfg.hidden)
                    + batch * states)

    plan = attention_plan(cfg, length)
    rule = delta_rule.plan(cfg.key_heads, length, cfg.key_dim,
                           cfg.value_dim, cfg.chunk, cfg.dtype)
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
                            ("biscotti_gdn_walked_layers",
                             "delta-net layers whose mixer a block of more "
                             "than one peer runs a peer at a time (static; "
                             "beside biscotti_sim_peer_block: above 1 "
                             "there, the walk ran)",
                             cfg.layer_types.count("gdn"), {})]})
