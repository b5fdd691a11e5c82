"""Plain reference of Qwen3-Next-80B-A3B-Instruct's decoder share (gated
delta-net and gated attention layers 3 : 1, a sparse MLP on every layer)
with rank-r adapters, and of one Biscotti round on it: forward, next-token
loss, the adapters' gradient, the clipped step, the DP noise, Krum, the
sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json),
Gated Delta Networks (arXiv:2412.06464) and the equations of ISSUE 38;
imports nothing of biscotti_tpu. Straight `jax.numpy` in ONE dtype (float64
in the CPU tests; float32 under `jax.default_matmul_precision("highest")`
on the chip): no kernels, NO CHUNKS and no solve, no sort and no grouped
product. Every width as published. Block l (0-based) on x [T, 2048], with
rms0(x, w) = x / sqrt(mean x^2 + 1e-6) * (1 + w):

    h  = x + mixer_l(rms0(x, w_in_norm))    gated attention where (l + 1) %
                                            4 == 0, else the gated delta net
    x' = h + moe(rms0(h, w_post_norm))      every layer

  gated delta net (16 key heads of 128, 32 value heads of 128):
    [q | k | v | z] = u W_qkvz  [2048, 12288], a key head g at a time: q_g
      [128], k_g [128], v_{2g,2g+1} [2 x 128], z_{2g,2g+1} [2 x 128]
    [b | a] = u W_ba  [2048, 64], a key head at a time: b_{2g,2g+1}, a_{..}
    [q | k | v] <- silu(causal depthwise conv, 4 taps, no bias, over the
      8,192 channels of [q | k | v])
    beta_t = sigmoid(b_t);  g_t = -exp(A_log) softplus(a_t + dt_bias) <= 0
    q_t <- q_t / |q_t| x 128^-0.5;  k_t <- k_t / |k_t|  (l2, eps 1e-6, a key
      head at a time; key head g serves value heads 2g, 2g + 1)
    a value head, S_0 = 0 in R^{128 x 128}, a window one sequence, A TOKEN
    AT A TIME (`lax.scan` over T):
        S = exp(g_t) S_{t-1};  d = beta_t (v_t - S^T k_t);
        S_t = S + k_t d^T;  o_t = S_t^T q_t
    y = rms(o_t, w_norm [128]) * silu(z_t)  (weight w, NOT 1 + w; norm
      first, gate second);  out = concat_heads(y) W_out  [4096, 2048]
  gated attention (16 query heads on 2 key/value heads of 256, causal):
    [q | gate] = u W_q  [2048, 8192], a head [q_h [256] | gate_h [256]];
      k = u W_k, v = u W_v  [2048, 512] each
    q_h <- rms0(q_h, w_qn [256]);  k_j <- rms0(k_j, w_kn [256])
    rotate-half rotary on the first 64 of the 256, theta 1e7, no scaling
    o_h = softmax(q_h k_j^T / 16 + causal) v_j, the scores whole
    out = concat_heads(o_h * sigmoid(gate_h)) W_o  [4096, 2048]
  MoE: p = softmax(u W_r) over ALL 512; the 10 largest over their sum;
    expert e (a loop over the held ones, every token through each, its
    coefficient zero where it was not chosen): (silu(u W_g^e) * (u W_u^e))
    W_d^e; plus sigmoid(u w_sg) swiglu_shared(u)
  final rms0, then an UNTIED head over the held rows. NO multi-token-
  prediction head (the catalog's config has no key for one).
  adapters: x W + (alpha / r)(x A) B on W_qkvz and W_out of the delta-net
  layers and on q, k, v, o of the attention layers.

So that a peer's gradient fits the chip beside the program's 10.85 GB base,
it runs a peer at a time, a layer at a time (`jax.checkpoint` around each)
and the recurrence a SEGMENT of tokens at a time (`jax.checkpoint` around
each: still token by token, no arithmetic differs).

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (`PUBLISHED` of drivers/device_round_gdn.py)
            at the layers held, plus `first_expert`, `lora_rank`,
            `lora_alpha`
  frozen    embed [V, H], head [H, V], final_norm [H], layers[l]: norm,
            mlp_norm, router [H, E_all], shared {w_gate, w_up, w_down},
            shared_gate [H, 1], experts {w_gate [E, H, F], w_up, w_down},
            lora_a, and for the delta net w_qkvz, w_ba, conv_w [K, C],
            a_log, dt_bias [heads], gate_norm [E_v], w_out; for attention
            wq, wk, wv, wo, q_norm, k_norm
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order out, qkvz (delta net) or k, o, q, v
            (attention): the ravel of {"layers": [{...}]}, float

`variant` names a departure, for the controls that must come out not
correct: {"delta": False} (d = beta v: plain gated linear attention),
{"beta": 1.0}, {"decay": "bfloat16", "chunk": L} (the log-decays g and
their running sum inside each chunk of L held in bfloat16, a step's decay
the exp of the difference of two such sums), {"carry": False, "chunk": L}
(the state set to zero where each chunk starts), {"l2norm": False},
{"gate_first": True}, {"zero_centred": False} (every rms0 read with weight
w), {"output_gate": False}, {"shared_gate": False}, {"rotary": "full"}
(all 256 turned), {"renormalise": False}.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import bf16, clipped_step  # noqa: F401  (plain numpy helpers)

ADAPTED = {"gdn": ("out", "qkvz"), "attention": ("k", "o", "q", "v")}
SEGMENT = 32  # tokens of the recurrence whose states the backward holds


def kinds(spec):
    return ["attention" if (at + 1) % spec["full_attention_interval"] == 0
            else "gdn" for at in range(spec["num_hidden_layers"])]


def widths(spec, kind):
    """{projection: (in, out)} of a layer of `kind`."""
    hidden = spec["hidden_size"]
    if kind == "gdn":
        keys = spec["linear_num_key_heads"] * spec["linear_key_head_dim"]
        values = (spec["linear_num_value_heads"]
                  * spec["linear_value_head_dim"])
        return {"qkvz": (hidden, 2 * keys + 2 * values),
                "out": (values, hidden)}
    n = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    return {"q": (hidden, 2 * n), "k": (hidden, kv), "v": (hidden, kv),
            "o": (n, hidden)}


def layout(spec):
    """[(name, shape)] of the wire vector's leaves, in order."""
    return [(f"layers[{at}].{name}",
             (spec["lora_rank"], widths(spec, kind)[name][1]))
            for at, kind in enumerate(kinds(spec))
            for name in ADAPTED[kind]]


def num_params(spec):
    return sum(math.prod(shape) for _, shape in layout(spec))


def leaves(spec, flat):
    """[(name, the leaf's slice of `flat`)]."""
    out, at = [], 0
    for name, shape in layout(spec):
        n = math.prod(shape)
        out.append((name, flat[..., at:at + n]))
        at += n
    return out


def unflatten(spec, flat, dtype):
    """[{projection: B [r, out]}] layer by layer."""
    per_layer = [{} for _ in kinds(spec)]
    for (name, shape), (_, piece) in zip(layout(spec),
                                         leaves(spec, jnp.asarray(flat))):
        at = int(name[len("layers["):name.index("]")])
        per_layer[at][name.split(".")[1]] = piece.reshape(shape).astype(dtype)
    return per_layer


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def delta_rule(q, k, v, g, beta, variant):
    """o [T, H, E] of ONE window, a token at a time from S = 0: q, k [T,
    G, D] (normalised), v [T, H, E], g, beta [T, H]; value head h reads
    key head h // (H / G)."""
    t, heads, e = v.shape
    each = heads // q.shape[1]
    q, k = (jnp.repeat(a, each, axis=1) for a in (q, k))      # [T, H, D]
    low = variant.get("decay") == "bfloat16"
    carried = variant.get("carry", True)
    chunk = variant.get("chunk", t)

    def step(carry, item):
        state, cum = carry
        q_t, k_t, v_t, g_t, beta_t, first = item
        if low:  # the decay from two bfloat16 running sums of a chunk;
            # `reduce_precision`, as XLA drops a cast there and back
            r = lambda a: jax.lax.reduce_precision(a, 8, 7)  # noqa: E731
            before = jnp.where(first, 0.0, cum)
            cum = r(before + r(g_t))
            decay = jnp.exp(cum - before)
        else:
            decay = jnp.exp(g_t)
        if not carried:
            state = jnp.where(first, 0.0, state)
        state = decay[:, None, None] * state                  # [H, D, E]
        delta = v_t
        if variant.get("delta", True):
            delta = v_t - jnp.einsum("hde,hd->he", state, k_t)
        delta = beta_t[:, None] * delta
        state = state + k_t[:, :, None] * delta[:, None, :]
        return (state, cum), jnp.einsum("hde,hd->he", state, q_t)

    def segment(carry, items):
        return jax.lax.scan(step, carry, items)

    size = math.gcd(t, SEGMENT)
    first = (jnp.arange(t) % chunk) == 0
    items = jax.tree.map(lambda a: a.reshape((t // size, size) + a.shape[1:]),
                         (q, k, v, g, beta, first))
    start = (jnp.zeros((heads, q.shape[-1], e), v.dtype),
             jnp.zeros((heads,), v.dtype))
    _, out = jax.lax.scan(jax.checkpoint(segment), start, items)
    return out.reshape(t, heads, e)


def delta_net(spec, x, w, lora, f, variant):
    """The gated delta-net mixer on the normed x [b, T, H]."""
    g, h = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    each, taps = h // g, spec["linear_conv_kernel_dim"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w_" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    mixed = adapted(x, "qkvz").reshape(b, t, g, 2 * dk + 2 * each * dv)
    ba = (x @ f(w["w_ba"])).reshape(b, t, g, 2 * each)
    q, k = mixed[..., :dk], mixed[..., dk:2 * dk]             # [b, T, g, dk]
    v = mixed[..., 2 * dk:2 * dk + each * dv].reshape(b, t, h, dv)
    z = mixed[..., 2 * dk + each * dv:].reshape(b, t, h, dv)
    beta = jax.nn.sigmoid(ba[..., :each].reshape(b, t, h))
    a = ba[..., each:].reshape(b, t, h)
    # causal depthwise conv over [q | k | v] flat: out_t = sum_i weight[i]
    # in_{t + i - (K - 1)}, no bias
    qkv = jnp.concatenate([q.reshape(b, t, -1), k.reshape(b, t, -1),
                           v.reshape(b, t, -1)], -1)
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(f(w["conv_w"])[i] * padded[:, i:i + t]
                          for i in range(taps)))
    q = qkv[..., :g * dk].reshape(b, t, g, dk)
    k = qkv[..., g * dk:2 * g * dk].reshape(b, t, g, dk)
    v = qkv[..., 2 * g * dk:].reshape(b, t, h, dv)
    if variant.get("beta") is not None:
        beta = jnp.full_like(beta, variant["beta"])
    decay = -jnp.exp(f(w["a_log"])) * jax.nn.softplus(a + f(w["dt_bias"]))
    if variant.get("l2norm", True):
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * dk ** -0.5
    out = jax.vmap(lambda *a: delta_rule(*a, variant))(q, k, v, decay, beta)
    eps = spec["rms_norm_eps"]
    if variant.get("gate_first", False):
        out = rms_norm(out * jax.nn.silu(z), f(w["gate_norm"]), eps)
    else:
        out = rms_norm(out, f(w["gate_norm"]), eps) * jax.nn.silu(z)
    return adapted(out.reshape(b, t, h * dv), "out")


def attention(spec, x, w, lora, f, variant, rms0):
    """The gated attention mixer on the normed x [b, T, H]."""
    n, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["head_dim"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    wide = adapted(x, "q").reshape(b, t, n, 2 * dh)
    q, gate = wide[..., :dh], wide[..., dh:]
    k = adapted(x, "k").reshape(b, t, kv, dh)
    v = adapted(x, "v").reshape(b, t, kv, dh)
    q, k = rms0(q, f(w["q_norm"])), rms0(k, f(w["k_norm"]))
    rot = dh if variant.get("rotary") == "full" \
        else int(dh * spec["partial_rotary_factor"])
    inv = 1.0 / float(spec["rope_theta"]) ** (np.arange(0, rot, 2) / rot)
    angles = np.outer(np.arange(t), inv)
    cos, sin = f(np.cos(angles))[:, None, :], f(np.sin(angles))[:, None, :]

    def turn(u):
        a, b_ = u[..., :rot // 2], u[..., rot // 2:rot]
        return jnp.concatenate([a * cos - b_ * sin, b_ * cos + a * sin,
                                u[..., rot:]], -1)

    q, k = turn(q), turn(k)
    k, v = (jnp.repeat(u, n // kv, axis=2) for u in (k, v))
    scores = jnp.einsum("bind,bjnd->bnij", q, k) / math.sqrt(dh)
    seen = np.arange(t)[None, :] <= np.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("bnij,bjnd->bind", probs, v)
    if variant.get("output_gate", True):
        out = out * jax.nn.sigmoid(gate)
    return adapted(out.reshape(b, t, n * dh), "o")


def layer(spec, kind, h, w, lora, dtype, variant):
    """A layer of `kind` on h [b, T, H] with its frozen weights `w` and
    adapters `lora`: (h', the router's (experts [N, k], probabilities [N,
    E_all]))."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    eps = spec["rms_norm_eps"]
    centre = 1.0 if variant.get("zero_centred", True) else 0.0

    def rms0(x, weight):
        return rms_norm(x, centre + weight, eps)

    x = rms0(h, f(w["norm"]))
    if kind == "gdn":
        h = h + delta_net(spec, x, w, lora, f, variant)
    else:
        h = h + attention(spec, x, w, lora, f, variant, rms0)
    b, t, _ = h.shape
    x = rms0(h, f(w["mlp_norm"])).reshape(b * t, -1)
    probs = jax.nn.softmax(x @ f(w["router"]), -1)
    top_p, top_i = jax.lax.top_k(probs, spec["num_experts_per_tok"])
    coef = top_p
    if spec["norm_topk_prob"] and variant.get("renormalise", True):
        coef = top_p / jnp.sum(top_p, -1, keepdims=True)

    def one_expert(total, item):
        e, w_gate, w_up, w_down = item
        mine = jnp.sum(jnp.where(top_i == spec["first_expert"] + e, coef,
                                 0.0), -1)
        return total + mine[:, None] * swiglu(x, f(w_gate), f(w_up),
                                              f(w_down)), None

    experts = w["experts"]
    m, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (jnp.arange(experts["w_gate"].shape[0]),
                         experts["w_gate"], experts["w_up"],
                         experts["w_down"]))
    shared = swiglu(x, *(f(w["shared"][name]) for name in (
        "w_gate", "w_up", "w_down")))
    if variant.get("shared_gate", True):
        shared = jax.nn.sigmoid(x @ f(w["shared_gate"])) * shared
    return h + (m + shared).reshape(b, t, -1), (top_i, probs)


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T], and the router's (experts
    [N, k], probabilities [N, E_all]) of every layer. A layer at a time:
    each layer's backward recomputes that layer's own forward."""
    variant = variant or {}
    h = jnp.asarray(frozen["embed"], dtype)[tokens]          # [b, T, H]
    picks = []
    for at, kind in enumerate(kinds(spec)):
        def one(h, w, lora, kind=kind):
            return layer(spec, kind, h, w, lora, dtype, variant)

        h, picked = jax.checkpoint(one)(h, frozen["layers"][at],
                                        adapters[at])
        picks.append(picked)
    centre = 1.0 if variant.get("zero_centred", True) else 0.0
    h = rms_norm(h, centre + jnp.asarray(frozen["final_norm"], dtype),
                 spec["rms_norm_eps"])
    return h @ jnp.asarray(frozen["head"], dtype), picks


def loss(spec, frozen, adapters, tokens, labels, dtype, variant=None):
    """Mean next-token cross-entropy over the held vocabulary."""
    logits, _ = forward(spec, frozen, adapters, tokens, dtype, variant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


_COMPILED = {}


def compiled(spec, dtype, variant=None):
    """(gradient, forward) as jitted functions of (frozen, w, tokens[,
    labels]): d loss / d w flat in `dtype`, and (logits, picks). The
    frozen tree is an ARGUMENT: closed over, its gigabytes would be
    constants of the program. One pair a (spec, dtype, variant): a second
    check of one process traces nothing anew."""
    key = (repr(sorted(spec.items(), key=str)), jnp.dtype(dtype).name,
           repr(sorted((variant or {}).items())))
    if key not in _COMPILED:
        def of(flat, frozen, tokens, labels):
            return loss(spec, frozen, unflatten(spec, flat, dtype), tokens,
                        labels, dtype, variant)

        def gradient(frozen, w, tokens, labels):
            return jax.grad(of)(jnp.asarray(w, dtype), frozen, tokens,
                                labels)

        def run(frozen, w, tokens):
            return forward(spec, frozen, unflatten(spec, w, dtype), tokens,
                           dtype, variant)

        _COMPILED[key] = (jax.jit(gradient), jax.jit(run))
    return _COMPILED[key]


def reference_round(spec, rnd, seed, it, w, stake, frozen, shard_rows, x_val,
                    y_val, dtype, variant=None, accept_from=None):
    """One round from adapters `w` and ledger `stake`.

    rnd: n, s, rows, batch, clip, eta, epsilon, delta, noising,
    verification, stake_unit. shard_rows(peer, idx) -> (tokens [B, T],
    labels [B, T]). The draws are the stated stream's
    (`reference/round.py:draws`), the noise scaled by eta as the step is.
    `variant` may also hold {"store": "bfloat16"}: the adapters, the
    deltas and the running sum held in bfloat16 (a control). Returns
    sampled, deltas, scores, accept, agg, w_next, stake_next, err."""
    from . import krum as rkrum
    from . import round as rround

    variant = dict(variant or {})
    low = variant.pop("store", None) == "bfloat16"
    q = bf16 if low else (lambda a: np.asarray(a, np.float64))
    d = num_params(spec)
    sigma = rround.sigma_for(rnd["epsilon"], rnd["delta"]) \
        if rnd["noising"] else 0.0
    cidx, idx, noise = rround.draws(seed, it, rnd["n"], rnd["s"],
                                    rnd["rows"], rnd["batch"], d, sigma)
    gradient, run = compiled(spec, dtype, variant)
    kept = np.asarray(w, np.float64)
    w = q(kept)
    deltas = np.empty((rnd["s"], d), np.float64)
    for j, peer in enumerate(cidx):  # a peer at a time
        tokens, labels = shard_rows(int(peer), idx[j])
        deltas[j] = q(clipped_step(
            gradient(frozen, w, jnp.asarray(tokens), jnp.asarray(labels)),
            rnd["clip"], rnd["eta"]))
    noised = deltas if noise is None else q(deltas + rnd["eta"] * q(noise))
    if rnd["verification"]:
        scores, accept = rkrum.krum_oracle(noised, rnd["s"] // 2)
    else:
        scores, accept = np.zeros(rnd["s"]), np.ones(rnd["s"], bool)
    used = accept if accept_from is None else np.asarray(accept_from, bool)
    if low:
        agg = np.zeros(d)
        for row in deltas[used]:  # in order, as a low-precision sum runs
            agg = q(agg + row)
        w_next = q(w + agg)
    else:
        agg = deltas[used].sum(axis=0)
        w_next = kept + agg
    stake_next = np.array(stake, np.int64)
    np.add.at(stake_next, cidx, np.where(used, rnd["stake_unit"],
                                         -rnd["stake_unit"]))
    logits, _ = run(frozen, w_next, jnp.asarray(x_val))
    err = float(jnp.mean(jnp.argmax(logits, -1) != jnp.asarray(y_val)))
    return {"sampled": cidx, "deltas": deltas, "scores": scores,
            "accept": accept, "agg": agg, "w_next": w_next,
            "stake_next": stake_next, "err": err}
