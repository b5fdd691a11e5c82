"""Plain reference of Granite-4.0-H-Micro's Mamba-2 / attention hybrid with
rank-r adapters, and of one Biscotti round on it: forward, next-token
loss, the adapters' gradient, the clipped step, the DP noise, Krum, the
sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json)
and the equations of ISSUE 33; imports nothing of biscotti_tpu. Straight
`jax.numpy` in ONE dtype (float64 in the CPU tests; float32 under
`jax.default_matmul_precision("highest")` on the chip): no kernels and NO
CHUNKS. The state-space layer is the recurrence as written, a token at a
time from a zero state (`lax.scan` over T):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t;   y_t = S_t C_t + D_h x_t

which is the point: it shares no algorithm with the chunked scan it checks.
So that a peer's gradient fits the chip beside the program's 6.4 GB base,
it runs a peer at a time, a layer at a time (`jax.checkpoint` around each
layer) and the recurrence a SEGMENT of tokens at a time (`jax.checkpoint`
around each: the backward keeps one state a segment and a segment's
states, not all T; still token by token, no arithmetic differs).

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (hidden_size, layer_types,
            num_attention_heads, num_key_value_heads, mamba_n_heads,
            mamba_d_head, mamba_d_state, mamba_d_conv, mamba_chunk_size,
            embedding_multiplier, residual_multiplier, attention_multiplier,
            logits_scaling, rms_norm_eps, rope_theta), plus `lora_rank`,
            `lora_alpha`
  frozen    embed [V, H] (the head too: tie_word_embeddings), final_norm
            [H], layers[l]: norm, mlp_norm, mlp {w_gate, w_up, w_down},
            lora_a, and for "mamba" w_in [H, 2 d_inner + 2 N + heads],
            conv_w [K, d_inner + 2 N], conv_b, dt_bias, a_log, d [heads],
            gate_norm [d_inner], w_out [d_inner, H]; for "attention" wq,
            wk, wv, wo
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order in, out (mamba) or k, o, q, v
            (attention): the ravel of {"layers": [{...}]}, float

`variant` names a departure, for the controls that must come out not
correct: {"decay": "bfloat16"} (the log-decays dt A and their running sum
inside each chunk of `mamba_chunk_size` held in bfloat16, a step's decay
the exp of the difference of two such sums: what a chunked scan with
bfloat16 cumulative sums computes), {"carry": False} (the state set to
zero where each chunk starts), {"d": False}, {"conv_bias": False},
{"dt_bias": False}, {"gate_first": False} (the norm, then the gate),
{"residual": 1.0}, {"embedding": 1.0}, {"logits_scaling": 1.0},
{"attention": 0.125} (1 / sqrt(64)), {"rotary": True} (rotate-half rotary
at `rope_theta` on q and k).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import bf16, clipped_step  # noqa: F401  (plain numpy helpers)

ADAPTED = {"mamba": ("in", "out"), "attention": ("k", "o", "q", "v")}
SEGMENT = 32  # tokens of the recurrence whose states the backward holds


def inner_width(spec):
    return spec["mamba_n_heads"] * spec["mamba_d_head"]


def widths(spec, kind):
    """{projection: (in, out)} of a layer of `kind`."""
    hidden = spec["hidden_size"]
    if kind == "mamba":
        inner = inner_width(spec)
        return {"in": (hidden, 2 * inner + 2 * spec["mamba_d_state"]
                       + spec["mamba_n_heads"]),
                "out": (inner, hidden)}
    dh = hidden // spec["num_attention_heads"]
    n, kv = spec["num_attention_heads"] * dh, spec["num_key_value_heads"] * dh
    return {"q": (hidden, n), "k": (hidden, kv), "v": (hidden, kv),
            "o": (n, hidden)}


def layout(spec):
    """[(name, shape)] of the wire vector's leaves, in order."""
    return [(f"layers[{at}].{name}",
             (spec["lora_rank"], widths(spec, kind)[name][1]))
            for at, kind in enumerate(spec["layer_types"])
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
    per_layer = [{} for _ in spec["layer_types"]]
    for (name, shape), (_, piece) in zip(layout(spec),
                                         leaves(spec, jnp.asarray(flat))):
        at = int(name[len("layers["):name.index("]")])
        per_layer[at][name.split(".")[1]] = piece.reshape(shape).astype(dtype)
    return per_layer


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def recurrence(x, dt, a, b, c, chunk, variant):
    """y [T, heads, P] of ONE window, a token at a time from S = 0: x [T,
    heads, P], dt [T, heads], a [heads], b, c [T, N]."""
    t, heads, p = x.shape
    low = variant.get("decay") == "bfloat16"
    carried = variant.get("carry", True)

    def step(carry, item):
        state, cum = carry
        x_t, dt_t, b_t, c_t, first = item
        if low:  # the decay from two bfloat16 running sums of a chunk;
            # `reduce_precision`, as XLA drops a cast there and back
            r = lambda v: jax.lax.reduce_precision(v, 8, 7)  # noqa: E731
            before = jnp.where(first, 0.0, cum)
            cum = r(before + r(dt_t * a))
            decay = jnp.exp(cum - before)
        else:
            decay = jnp.exp(dt_t * a)
        if not carried:
            state = jnp.where(first, 0.0, state)
        state = (decay[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return (state, cum), state @ c_t

    def segment(carry, items):
        return jax.lax.scan(step, carry, items)

    size = math.gcd(t, SEGMENT)
    first = (jnp.arange(t) % chunk) == 0
    items = jax.tree.map(lambda v: v.reshape((t // size, size) + v.shape[1:]),
                         (x, dt, b, c, first))
    start = (jnp.zeros((heads, p, b.shape[-1]), x.dtype),
             jnp.zeros((heads,), x.dtype))
    _, y = jax.lax.scan(jax.checkpoint(segment), start, items)
    return y.reshape(t, heads, p)


def mamba(spec, x, w, lora, f, variant):
    """The state-space mixer on the normed x [b, T, H]."""
    inner, n = inner_width(spec), spec["mamba_d_state"]
    heads, taps = spec["mamba_n_heads"], spec["mamba_d_conv"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w_" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    mixed = adapted(x, "in")
    z, xbc, dt = (mixed[..., :inner], mixed[..., inner:inner + inner + 2 * n],
                  mixed[..., inner + inner + 2 * n:])
    # causal depthwise conv: out_t = sum_k weight[k] in_{t + k - (K - 1)}
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(f(w["conv_w"])[k] * padded[:, k:k + t] for k in range(taps))
    if variant.get("conv_bias", True):
        conv = conv + f(w["conv_b"])
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :inner].reshape(b, t, heads, inner // heads)
    bs, cs = xbc[..., inner:inner + n], xbc[..., inner + n:]
    if variant.get("dt_bias", True):
        dt = dt + f(w["dt_bias"])
    dt = jax.nn.softplus(dt)          # time_step_limit (0, inf): no clamp
    a = -jnp.exp(f(w["a_log"]))
    y = jax.vmap(lambda *v: recurrence(*v, spec["mamba_chunk_size"],
                                       variant),
                 in_axes=(0, 0, None, 0, 0))(xs, dt, a, bs, cs)
    if variant.get("d", True):
        y = y + f(w["d"])[:, None] * xs
    y = y.reshape(b, t, inner)
    eps = spec["rms_norm_eps"]
    if variant.get("gate_first", True):
        y = rms_norm(y * jax.nn.silu(z), f(w["gate_norm"]), eps)
    else:
        y = rms_norm(y, f(w["gate_norm"]), eps) * jax.nn.silu(z)
    return adapted(y, "out")


def attention(spec, x, w, lora, f, variant):
    """Grouped-query attention without rotary on the normed x [b, T, H]."""
    n, kv = spec["num_attention_heads"], spec["num_key_value_heads"]
    dh = spec["hidden_size"] // n
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, name):
        return x @ f(w["w" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    q = adapted(x, "q").reshape(b, t, n, dh)
    k = adapted(x, "k").reshape(b, t, kv, dh)
    v = adapted(x, "v").reshape(b, t, kv, dh)
    if variant.get("rotary", False):
        inv = 1.0 / float(spec["rope_theta"]) ** (np.arange(0, dh, 2) / dh)
        angles = np.outer(np.arange(t), inv)
        cos = f(np.concatenate([np.cos(angles)] * 2, -1))[:, None, :]
        sin = f(np.concatenate([np.sin(angles)] * 2, -1))[:, None, :]

        def turn(u):
            half = jnp.concatenate([-u[..., dh // 2:], u[..., :dh // 2]], -1)
            return u * cos + half * sin

        q, k = turn(q), turn(k)
    k, v = (jnp.repeat(u, n // kv, axis=2) for u in (k, v))
    scores = jnp.einsum("bind,bjnd->bnij", q, k) * variant.get(
        "attention", spec["attention_multiplier"])
    seen = np.arange(t)[None, :] <= np.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    out = jnp.einsum("bnij,bjnd->bind", probs, v)
    return adapted(out.reshape(b, t, n * dh), "o")


def layer(spec, at, h, w, lora, dtype, variant):
    """Layer `at` on h [b, T, H] with its frozen weights `w` and adapters
    `lora`."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    eps = spec["rms_norm_eps"]
    residual = variant.get("residual", spec["residual_multiplier"])
    mixer = mamba if spec["layer_types"][at] == "mamba" else attention
    h = h + residual * mixer(spec, rms_norm(h, f(w["norm"]), eps), w, lora,
                             f, variant)
    x = rms_norm(h, f(w["mlp_norm"]), eps)
    mlp = w["mlp"]
    return h + residual * (
        (jax.nn.silu(x @ f(mlp["w_gate"])) * (x @ f(mlp["w_up"])))
        @ f(mlp["w_down"]))


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T]; a layer at a time."""
    variant = variant or {}
    embed = jnp.asarray(frozen["embed"], dtype)
    h = variant.get("embedding", spec["embedding_multiplier"]) * embed[tokens]
    for at in range(len(spec["layer_types"])):
        def one(h, w, lora, at=at):
            return layer(spec, at, h, w, lora, dtype, variant)

        h = jax.checkpoint(one)(h, frozen["layers"][at], adapters[at])
    h = rms_norm(h, jnp.asarray(frozen["final_norm"], dtype),
                 spec["rms_norm_eps"])
    return (h @ embed.T) / variant.get("logits_scaling",
                                       spec["logits_scaling"])


def loss(spec, frozen, adapters, tokens, labels, dtype, variant=None):
    """Mean next-token cross-entropy over all the vocabulary."""
    logits = forward(spec, frozen, adapters, tokens, dtype, variant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


_COMPILED = {}


def compiled(spec, dtype, variant=None):
    """(gradient, forward) as jitted functions of (frozen, w, tokens[,
    labels]): d loss / d w flat in `dtype`, and the logits. The frozen
    tree is an ARGUMENT: closed over, its gigabytes would be constants of
    the program. One pair a (spec, dtype, variant): a second check of one
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
