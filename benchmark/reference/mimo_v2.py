"""Plain reference of MiMo-V2.5's decoder share (sliding-window attention
with a learned sink beside full attention, grouped queries at heads of 192
| 128, a sigmoid router with a choice bias) with rank-r adapters, and of one
Biscotti round on it: forward, next-token loss, the adapters' gradient, the
clipped step, the DP noise, Krum, the sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json) and the
equations of ISSUE 40; imports nothing of biscotti_tpu. Straight
`jax.numpy` in ONE dtype (float64 in the CPU tests; float32 under
`jax.default_matmul_precision("highest")` on the chip), as the sibling
references are: no kernels, no blocks, no sort and no grouped product.
Every width as published. Layer l (0-based) on x [T, 4096], kappa = full
where hybrid_layer_pattern[l] == 0, window where 1:

    u = rms(x, w_norm);  [q | k | v] = u W_qkv, flat: 64 query heads of
      192, kv_kappa key heads of 192 (4 full | 8 window), kv_kappa value
      heads of 128; query head h reads key/value head h // (64 / kv_kappa)
    rotate-half rotary on the first int(0.334 x 192) = 64 dimensions of
      every q and k head, theta 1e7 (full) | 1e4 (window)
    the scores a DENSE [T, T] matrix a head, s_ij = q_i . k_j / sqrt(192),
      -inf where j > i or, in a window layer, i - j >= 128; a window
      layer's has ONE MORE COLUMN, the head's sink b_h (the same for every
      row, not scaled); softmax over the row; the sink's column is dropped
    o_i = sum_j p_ij (0.707 v_j);  h = x + concat_heads(o) W_o
    u = rms(h, w_mlp_norm);  layer 0: x' = h + swiglu_dense(u);  else
      s = sigmoid(u W_r) over ALL 256; the eight largest of s + b; c_e =
      s_e / sum_chosen s; expert e (a loop over the held ones, every token
      through each, its coefficient zero where it was not chosen):
      x' = h + sum_e c_e (silu(u W_g^e) * (u W_u^e)) W_d^e;  no shared expert
    final rms, then an UNTIED head over the held rows. No multi-token-
    prediction layers, no encoders (the catalog's config has no key for
    either).
    adapters: x W + (alpha / r)(x A) B on W_qkv and W_o of every layer.

So that a peer's gradient fits the chip beside the program's 11.7 GB base,
it runs a peer at a time, a layer at a time (`jax.checkpoint` around each)
the attention a key/value head's group of query heads at a time (`lax.map`,
each rematerialised: one group's float32 scores are 0.27 GB at 2,048 tokens
where all 64 heads' are 1.07) and the experts' loop an expert at a time
(rematerialised, its weights cast inside the loop: the three stacks are 1
GB each in float32).

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (`PUBLISHED` of drivers/device_round_swa.py)
            with the two lists cut to the layers held, plus `first_expert`,
            `lora_rank`, `lora_alpha`
  frozen    embed [V, H], head [H, V], final_norm [H], layers[l]: norm,
            mlp_norm, w_qkv, wo, lora_a {o, qkv}, `sink` [64] (window
            layers), and `dense` {w_gate, w_up, w_down} or router [H,
            E_all], router_bias [E_all], experts {w_gate [E, H, F], w_up,
            w_down}: the E experts first_expert .. first_expert + E - 1
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order o, qkv (the ravel of {"layers":
            [{"o", "qkv"}]}), float

`variant` names a departure, for the controls that must come out not
correct: {"sink": False}; {"sink_on_full": True} (a full layer reads the
sinks of the window layer after it, raised by log(T / window): as heavy in
its rows as in a window's); {"window": False} (every layer causal);
{"window": 256}; {"rotary": "full"} (all 192 turned); {"theta": "one"}
(rope_theta in both kinds); {"value_scale": False}; {"kv_swapped": True}
(query head h reads the key/value head the OTHER kind's grouping gives it:
h // 16 in a window layer, (h // 8) % 4 in a full one); {"router":
"softmax"} (p = softmax, chosen by p + b, weighed by p); {"choice_bias":
False}; {"renormalise": False}.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import bf16, clipped_step  # noqa: F401  (plain numpy helpers)

ADAPTED = ("o", "qkv")  # the wire vector's order within a layer


def kinds(spec):
    """[(full | window, sparse)] of the layers held."""
    layers = spec["num_hidden_layers"]
    return [("window" if spec["hybrid_layer_pattern"][at] else "full",
             bool(spec["moe_layer_freq"][at])) for at in range(layers)]


def kv_heads(spec, kind):
    return spec["swa_num_key_value_heads" if kind == "window"
                else "num_key_value_heads"]


def widths(spec, kind):
    """{projection: (in, out)} of a layer of `kind`."""
    n, kv = spec["num_attention_heads"], kv_heads(spec, kind)
    return {"qkv": (spec["hidden_size"], (n + kv) * spec["head_dim"]
                    + kv * spec["v_head_dim"]),
            "o": (n * spec["v_head_dim"], spec["hidden_size"])}


def layout(spec):
    """[(name, shape)] of the wire vector's leaves, in order."""
    return [(f"layers[{at}].{name}",
             (spec["lora_rank"], widths(spec, kind)[name][1]))
            for at, (kind, _) in enumerate(kinds(spec)) for name in ADAPTED]


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
    """[{"o", "qkv"}: B [r, out]] layer by layer."""
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


def attention(spec, kind, x, w, lora, f, variant, sink):
    """The attention block of a layer of `kind` on the normed x [b, T, H];
    `sink` [heads] or None."""
    n, kv = spec["num_attention_heads"], kv_heads(spec, kind)
    d, e = spec["head_dim"], spec["v_head_dim"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    b, t, _ = x.shape

    def adapted(x, weight, name):
        return x @ f(weight) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    qkv = adapted(x, w["w_qkv"], "qkv")
    q = qkv[..., :n * d].reshape(b, t, n, d)
    k = qkv[..., n * d:(n + kv) * d].reshape(b, t, kv, d)
    v = qkv[..., (n + kv) * d:].reshape(b, t, kv, e)
    rot = d if variant.get("rotary") == "full" \
        else int(spec["partial_rotary_factor"] * d)
    theta = spec["swa_rope_theta"] if kind == "window" \
        and variant.get("theta") != "one" else spec["rope_theta"]
    inv = 1.0 / float(theta) ** (np.arange(0, rot, 2) / rot)
    angles = np.outer(np.arange(t), inv)
    cos, sin = f(np.cos(angles))[:, None, :], f(np.sin(angles))[:, None, :]

    def turn(u):
        a, b_ = u[..., :rot // 2], u[..., rot // 2:rot]
        return jnp.concatenate([a * cos - b_ * sin, b_ * cos + a * sin,
                                u[..., rot:]], -1)

    q, k = turn(q), turn(k)
    if variant.get("value_scale", True):
        v = spec["attention_value_scale"] * v
    group = n // kv
    serves = np.arange(n) // group              # a query head's key/value head
    if variant.get("kv_swapped"):
        serves = (np.arange(n) // (2 * group if kind == "window"
                                   else group // 2)) % kv
    serves = jnp.asarray(serves)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    window = variant.get("window", True)
    if kind == "window" and window:
        seen = seen & (i - j < (spec["sliding_window"] if window is True
                                else window))

    def some_heads(heads):
        """[b, T, g, e] of the query heads `heads` [g]: their scores whole,
        [b, g, T, T (+ 1: the sink's column)]."""
        scores = jnp.einsum("bind,bjnd->bnij", jnp.take(q, heads, axis=2),
                            jnp.take(k, serves[heads], axis=2)) / math.sqrt(d)
        scores = jnp.where(seen, scores, -jnp.inf)
        if sink is not None:
            column = jnp.broadcast_to(sink[heads][None, :, None, None],
                                      scores.shape[:-1] + (1,))
            scores = jnp.concatenate([scores, column], -1)
        probs = jax.nn.softmax(scores, -1)[..., :t]
        return jnp.einsum("bnij,bjne->bine", probs,
                          jnp.take(v, serves[heads], axis=2))

    out = jax.lax.map(jax.checkpoint(some_heads),
                      jnp.arange(n).reshape(kv, group))  # [kv, b, T, g, e]
    out = jnp.moveaxis(out, 0, 2).reshape(b, t, n * e)
    return adapted(out, w["wo"], "o")


def layer(spec, at, h, w, lora, dtype, variant, borrowed=None):
    """Layer `at` on h [b, T, H] with its frozen weights `w` and adapters
    `lora`: (h', the router's (experts [N, k], what they were chosen by [N,
    E_all]), None on the dense layer). `borrowed`: the sinks of the window
    layer that follows (the `sink_on_full` control alone reads them)."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    eps = spec["layernorm_epsilon"]
    kind, sparse = kinds(spec)[at]
    b, t, _ = h.shape
    sink = None
    if kind == "window" and variant.get("sink", True):
        sink = f(w["sink"])
    elif kind == "full" and variant.get("sink_on_full"):
        sink = f(borrowed) + math.log(t / spec["sliding_window"])
    x = rms_norm(h, f(w["norm"]), eps)
    h = h + attention(spec, kind, x, w, lora, f, variant, sink)

    x = rms_norm(h, f(w["mlp_norm"]), eps).reshape(b * t, -1)
    if not sparse:
        return h + swiglu(x, *(f(w["dense"][name]) for name in (
            "w_gate", "w_up", "w_down"))).reshape(b, t, -1), None
    logits = x @ f(w["router"])
    scores = jax.nn.softmax(logits, -1) \
        if variant.get("router") == "softmax" else jax.nn.sigmoid(logits)
    chosen_by = scores
    if variant.get("choice_bias", True):
        chosen_by = scores + f(w["router_bias"])
    _, top_i = jax.lax.top_k(chosen_by, spec["num_experts_per_tok"])
    coef = jnp.take_along_axis(scores, top_i, -1)
    if spec["norm_topk_prob"] and variant.get("renormalise", True):
        coef = coef / jnp.sum(coef, -1, keepdims=True)

    @jax.checkpoint  # an expert's activations are made again, not kept
    def one_expert(total, item):
        # the barrier keeps the cast to `dtype` on this expert's slices: the
        # compiler otherwise casts the three whole stacks ahead of the loop
        # (1 GB each in float32 at the published size)
        e, w_gate, w_up, w_down = jax.lax.optimization_barrier(item)
        mine = jnp.sum(jnp.where(top_i == spec["first_expert"] + e, coef,
                                 0.0), -1)
        return total + mine[:, None] * swiglu(x, f(w_gate), f(w_up),
                                              f(w_down)), None

    experts = w["experts"]
    m, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (jnp.arange(experts["w_gate"].shape[0]),
                         experts["w_gate"], experts["w_up"],
                         experts["w_down"]))
    return h + m.reshape(b, t, -1), (top_i, chosen_by)


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T], and the router's (experts
    [N, k], what they were chosen by [N, E_all]) of every sparse layer. A
    layer at a time: each layer's backward recomputes that layer's own
    forward."""
    variant = variant or {}
    h = jnp.asarray(frozen["embed"], dtype)[tokens]          # [b, T, H]
    picks = []
    layers = frozen["layers"]
    for at in range(len(kinds(spec))):
        borrowed = next((layers[i]["sink"] for i in range(at + 1, len(layers))
                         if "sink" in layers[i]), None) \
            if variant.get("sink_on_full") else None

        def one(h, w, lora, borrowed, at=at):
            return layer(spec, at, h, w, lora, dtype, variant, borrowed)

        h, picked = jax.checkpoint(one)(h, layers[at], adapters[at], borrowed)
        if picked is not None:
            picks.append(picked)
    logits = rms_norm(h, jnp.asarray(frozen["final_norm"], dtype),
                      spec["layernorm_epsilon"]) @ jnp.asarray(
        frozen["head"], dtype)
    return logits, picks


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
