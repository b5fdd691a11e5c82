"""Plain reference of Olmo-Hybrid-7B's decoder stage (gated delta-net and
plain full-attention layers 3 : 1, a dense SwiGLU on every layer, the norm
on each sub-block's OUTPUT) with rank-r adapters, and of one Biscotti round
on it: forward, next-token loss, the adapters' gradient, the clipped step,
the DP noise, Krum, the sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json),
Gated Delta Networks (arXiv:2412.06464), arXiv:2411.12537 for the doubled
beta, the OLMo 2 family's block (arXiv:2501.00656) and the equations of
ISSUE 48; imports nothing of biscotti_tpu. Straight `jax.numpy` in ONE
dtype (float64 in the CPU tests; float32 under
`jax.default_matmul_precision("highest")` on the chip): no kernels, NO
CHUNKS and no solve, no zero columns, the scores whole. Every width as
published. Block l (0-based) on x [T, 3840], with rms(x, w) = x /
sqrt(mean x^2 + 1e-6) * w:

    h  = x + rms(mixer_l(x), w_1)     `full` where layer_types[l] ==
                                      "full_attention" (l % 4 == 3), else
                                      the gated delta net; the mixer reads
                                      x itself
    x' = h + rms(swiglu(h), w_2)      silu(h W_gate) * (h W_up), W_down

  gated delta net (30 key heads of 96, 30 value heads of 192):
    [q | k | v | z] = x W_qkvz  [3840, 17280], a key head g at a time: q_g
      [96], k_g [96], v_g [192], z_g [192]
    [b | a] = x W_ba  [3840, 60], a key head at a time: b_g, a_g
    [q | k | v] <- silu(causal depthwise conv, 4 taps, no bias, over the
      11,520 channels of [q | k | v])
    beta_t = 2 sigmoid(b_t)  (`linear_allow_neg_eigval`);
    g_t = -exp(A_log) softplus(a_t + dt_bias) <= 0
    q_t <- q_t / |q_t| x 96^-0.5;  k_t <- k_t / |k_t|  (l2, eps 1e-6)
    a value head, S_0 = 0 in R^{96 x 192}, a window one sequence, A TOKEN
    AT A TIME (`lax.scan` over T):
        S = exp(g_t) S_{t-1};  d = beta_t (v_t - S^T k_t);
        S_t = S + k_t d^T;  o_t = S_t^T q_t
    y = rms(o_t, w_o [192]) * silu(z_t)  (norm first, gate second);
    out = concat_heads(y) W_out  [5760, 3840]
  full attention (30 query heads on 30 key/value heads of 128, causal):
    q = rms(x W_q, w_q [3840]);  k = rms(x W_k, w_k [3840])  (over the
      whole projection, before the head split);  v = x W_v;  NO rotary
    o_h = softmax(q_h k_h^T / sqrt(128) + causal) v_h, the scores whole
    out = concat_heads(o_h) W_o
  final rms, then an UNTIED head over the whole vocabulary.
  adapters: x W + (alpha / r)(x A) B on W_qkvz and W_out of the delta-net
  layers and on q, k, v, o of the full layers.

So that a peer's gradient fits the chip beside the program's 8.21 GB base,
it runs a peer at a time, a layer at a time (`jax.checkpoint` around each,
the frozen leaves cast a layer at a time) and the recurrence a SEGMENT of
tokens at a time (`jax.checkpoint` around each: still token by token, no
arithmetic differs).

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (`PUBLISHED` of
            drivers/device_round_gdn_dense.py) at the layers held, plus
            `lora_rank`, `lora_alpha`
  frozen    embed [V, H], head [H, V], final_norm [H], layers[l]: norm
            (w_1), mlp_norm (w_2), mlp {w_gate, w_up, w_down}, lora_a, and
            for the delta net w_qkvz, w_ba, conv_w [K, C], a_log, dt_bias
            [heads], gate_norm [E], w_out; for full attention wq, wk, wv,
            wo, q_norm, k_norm [heads x head_dim]
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order out, qkvz (delta net) or k, o, q, v
            (full): the ravel of {"layers": [{...}]}, float

`variant` names a departure. Those of what `config.json` does not itself
state (the configuration's `assumed`), each a switch here: {"norm_first":
True} (the pre-norm order, h + f(rms(h, w)), in both sub-blocks of both
kinds of layer), {"layout": "flat"} (W_qkvz's columns read [q | k | v | z]
each over all heads, W_ba's [b | a]), {"gate_first": True}, {"rotary":
theta} (rotate-half over the whole head), {"qk_norm": False} (none) or
{"qk_norm": "head"} (a head at a time, the weight's slice). And the
controls that must come out not correct: {"delta": False} (d = beta v:
plain gated linear attention), {"beta": 1.0}, {"beta_scale": 1.0} (the
sigmoid not doubled), {"decay": "bfloat16", "chunk": L} (the log-decays g
and their running sum inside each chunk of L held in bfloat16, a step's
decay the exp of the difference of two such sums), {"carry": False,
"chunk": L} (the state set to zero where each chunk starts), {"l2norm":
False}.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import bf16, clipped_step  # noqa: F401  (plain numpy helpers)

ADAPTED = {"linear": ("out", "qkvz"), "full": ("k", "o", "q", "v")}
SEGMENT = 32  # tokens of the recurrence whose states the backward holds


def kinds(spec):
    return ["full" if kind == "full_attention" else "linear"
            for kind in spec["layer_types"][:spec["num_hidden_layers"]]]


def head_dim(spec):
    return spec["hidden_size"] // spec["num_attention_heads"]


def widths(spec, kind):
    """{projection: (in, out)} of a layer of `kind`."""
    hidden = spec["hidden_size"]
    if kind == "linear":
        keys = spec["linear_num_key_heads"] * spec["linear_key_head_dim"]
        values = (spec["linear_num_value_heads"]
                  * spec["linear_value_head_dim"])
        return {"qkvz": (hidden, 2 * keys + 2 * values),
                "out": (values, hidden)}
    kv = spec["num_key_value_heads"] * head_dim(spec)
    return {"q": (hidden, hidden), "k": (hidden, kv), "v": (hidden, kv),
            "o": (hidden, hidden)}


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
    """o [T, H, E] of ONE window, a token at a time from S = 0 in R^{D x
    E}: q, k [T, G, D] (normalised), v [T, H, E], g, beta [T, H]; value
    head h reads key head h // (H / G)."""
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
    """The gated delta-net mixer on x [b, T, H]."""
    g, h = spec["linear_num_key_heads"], spec["linear_num_value_heads"]
    dk, dv = spec["linear_key_head_dim"], spec["linear_value_head_dim"]
    each, taps = h // g, spec["linear_conv_kernel_dim"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w_" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    mixed, ba = adapted(x, "qkvz"), x @ f(w["w_ba"])
    if variant.get("layout") == "flat":  # each part over all heads
        cuts = np.cumsum([g * dk, g * dk, h * dv])
        q, k, v, z = jnp.split(mixed, cuts, -1)
        beta, a = ba[..., :h], ba[..., h:]
    else:                                # a key head at a time
        mixed = mixed.reshape(b, t, g, 2 * dk + 2 * each * dv)
        ba = ba.reshape(b, t, g, 2 * each)
        q, k = mixed[..., :dk], mixed[..., dk:2 * dk]
        v = mixed[..., 2 * dk:2 * dk + each * dv]
        z = mixed[..., 2 * dk + each * dv:]
        beta, a = ba[..., :each], ba[..., each:]
    z = z.reshape(b, t, h, dv)
    beta, a = beta.reshape(b, t, h), a.reshape(b, t, h)
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
    beta = variant.get("beta_scale", 2.0) * jax.nn.sigmoid(beta)
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


def attention(spec, x, w, lora, f, variant):
    """The full-attention mixer on x [b, T, H]."""
    n, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh, eps = head_dim(spec), spec["rms_norm_eps"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    q, k, v = adapted(x, "q"), adapted(x, "k"), adapted(x, "v")
    normed = variant.get("qk_norm", True)
    if normed == "head":  # a head at a time, each with its slice
        q = rms_norm(q.reshape(b, t, n, dh), f(w["q_norm"]).reshape(n, dh),
                     eps)
        k = rms_norm(k.reshape(b, t, kv, dh),
                     f(w["k_norm"]).reshape(kv, dh), eps)
    elif normed:         # over the whole projection
        q, k = rms_norm(q, f(w["q_norm"]), eps), rms_norm(k, f(w["k_norm"]),
                                                          eps)
    q = q.reshape(b, t, n, dh)
    k, v = k.reshape(b, t, kv, dh), v.reshape(b, t, kv, dh)
    if variant.get("rotary"):
        inv = 1.0 / float(variant["rotary"]) ** (np.arange(0, dh, 2) / dh)
        angles = np.outer(np.arange(t), inv)
        cos = f(np.concatenate([np.cos(angles)] * 2, -1))[:, None, :]
        sin = f(np.concatenate([np.sin(angles)] * 2, -1))[:, None, :]

        def turn(u):
            half = jnp.concatenate([-u[..., dh // 2:], u[..., :dh // 2]], -1)
            return u * cos + half * sin

        q, k = turn(q), turn(k)
    k, v = (jnp.repeat(u, n // kv, axis=2) for u in (k, v))
    scores = jnp.einsum("bind,bjnd->bnij", q, k) / math.sqrt(dh)
    seen = np.arange(t)[None, :] <= np.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("bnij,bjnd->bind", probs, v)
    return adapted(out.reshape(b, t, n * dh), "o")


def layer(spec, kind, h, w, lora, dtype, variant):
    """A layer of `kind` on h [b, T, H] with its frozen weights `w` and
    adapters `lora`."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    eps = spec["rms_norm_eps"]
    mixer = delta_net if kind == "linear" else attention
    mlp = [f(w["mlp"][name]) for name in ("w_gate", "w_up", "w_down")]
    if variant.get("norm_first", False):  # the pre-norm order
        h = h + mixer(spec, rms_norm(h, f(w["norm"]), eps), w, lora, f,
                      variant)
        return h + swiglu(rms_norm(h, f(w["mlp_norm"]), eps), *mlp)
    h = h + rms_norm(mixer(spec, h, w, lora, f, variant), f(w["norm"]), eps)
    return h + rms_norm(swiglu(h, *mlp), f(w["mlp_norm"]), eps)


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T]. A layer at a time: each
    layer's backward recomputes that layer's own forward."""
    variant = variant or {}
    h = jnp.asarray(frozen["embed"][tokens], dtype)          # [b, T, H]
    for at, kind in enumerate(kinds(spec)):
        def one(h, w, lora, kind=kind):
            return layer(spec, kind, h, w, lora, dtype, variant)

        h = jax.checkpoint(one)(h, frozen["layers"][at], adapters[at])
    h = rms_norm(h, jnp.asarray(frozen["final_norm"], dtype),
                 spec["rms_norm_eps"])
    return h @ jnp.asarray(frozen["head"], dtype)


def loss(spec, frozen, adapters, tokens, labels, dtype, variant=None):
    """Mean next-token cross-entropy over all the vocabulary."""
    logits = forward(spec, frozen, adapters, tokens, dtype, variant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


_COMPILED = {}


def compiled(spec, dtype, variant=None):
    """(gradient, forward) as jitted functions of (frozen, w, tokens[,
    labels]): d loss / d w flat in `dtype`, and the logits. The frozen tree
    is an ARGUMENT: closed over, its gigabytes would be constants of the
    program. One pair a (spec, dtype, variant): a second check of one
    process traces nothing anew."""
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
    logits = run(frozen, w_next, jnp.asarray(x_val))
    err = float(jnp.mean(jnp.argmax(logits, -1) != jnp.asarray(y_val)))
    return {"sampled": cidx, "deltas": deltas, "scores": scores,
            "accept": accept, "agg": agg, "w_next": w_next,
            "stake_next": stake_next, "err": err}
