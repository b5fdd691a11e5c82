"""Plain reference of Laguna-S-2.1's decoder share with rank-r adapters,
and of one Biscotti round on it: forward, next-token loss, the adapters'
gradient, the clipped step, the DP noise, Krum, the sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json) and
the equations of ISSUE 27; imports nothing of biscotti_tpu. Straight
`jax.numpy` in ONE dtype (float64 in the CPU tests; float32 under
`jax.default_matmul_precision("highest")` on the chip): no kernels, no
sort and no grouped product, and a layer at a time (`forward`). The experts are a loop over the
held ones with a mask (every held expert computes every token and the
token's coefficient for it, zero where it was not chosen, weighs the
result; written as a `scan` so that the traced program stays small), the
attention builds its mask explicitly, and a peer is computed at a time.

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (hidden_size, head_dim, num_key_value_heads,
            num_attention_heads_per_layer, layer_types, mlp_only_layers,
            sliding_window, num_experts_per_tok, moe_routed_scaling_factor,
            rope_parameters, rms_norm_eps) cut to the layers held, plus
            `first_expert`, `lora_rank`, `lora_alpha`
  frozen    embed [V, H], head [H, V], final_norm [H], layers[l]: attn_norm,
            mlp_norm, wq, wk, wv, wo, wgate, lora_a {q, k, v, o}, and
            `dense` {w_gate, w_up, w_down} or router [H, E_all], `shared`,
            `experts` {w_gate [E, H, F], w_up, w_down [E, F, H]}: the E
            experts first_expert .. first_expert + E - 1
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order k, o, q, v (the ravel of
            {"layers": [{"k", "o", "q", "v"}]}), float

`variant` names a departure, for the controls that must come out not
correct: {"fewer_experts": 1} (nine a token where the model takes ten),
{"shared": False}, {"window": False}, {"gate": False}, {"scale": 1.0}.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

ADAPTED = ("k", "o", "q", "v")  # the wire vector's order within a layer


def layout(spec):
    """[(name, shape)] of the wire vector's leaves, in order."""
    r, dh, hidden = spec["lora_rank"], spec["head_dim"], spec["hidden_size"]
    kv = spec["num_key_value_heads"] * dh
    out = []
    for at, n in enumerate(spec["num_attention_heads_per_layer"]):
        width = {"k": kv, "o": hidden, "q": n * dh, "v": kv}
        out += [(f"layers[{at}].{name}", (r, width[name]))
                for name in ADAPTED]
    return out


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
    """[{"k", "o", "q", "v"}: B [r, out]] layer by layer."""
    per_layer = []
    for (name, shape), (_, piece) in zip(layout(spec),
                                         leaves(spec, jnp.asarray(flat))):
        if name.endswith("." + ADAPTED[0]):
            per_layer.append({})
        per_layer[-1][name.split(".")[1]] = piece.reshape(shape).astype(dtype)
    return per_layer


def rotary(spec, kind, length):
    """(cos, sin) [T, rot / 2] in float64 numpy and the rotated width."""
    rope = spec["rope_parameters"][kind]
    rot = int(spec["head_dim"] * rope["partial_rotary_factor"])
    base = float(rope["rope_theta"])
    inv = np.array([base ** (-i / rot) for i in range(0, rot, 2)])
    factor = 1.0
    if rope.get("rope_type") == "yarn":
        original = rope["original_max_position_embeddings"]

        def dim_of(rotations):
            return (rot * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(dim_of(rope["beta_fast"])), 0)
        high = min(math.ceil(dim_of(rope["beta_slow"])), rot - 1)
        span = high - low if high != low else 0.001
        ramp = np.clip((np.arange(rot // 2) - low) / span, 0.0, 1.0)
        inv = (inv / rope["factor"]) * ramp + inv * (1.0 - ramp)
        factor = rope["attention_factor"]
    angles = np.outer(np.arange(length), inv)
    return np.cos(angles) * factor, np.sin(angles) * factor, rot


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rotate_half(x, cos, sin, rot):
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def layer(spec, at, h, w, lora, dtype, variant):
    """Layer `at` on h [b, T, H] with its frozen weights `w` and adapters
    `lora`: (h', the router's (experts [N, k], probabilities [N, E_all]),
    None on the dense layer)."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    dh, kv = spec["head_dim"], spec["num_key_value_heads"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    eps = spec["rms_norm_eps"]
    kind = spec["layer_types"][at]
    n = spec["num_attention_heads_per_layer"][at]
    b, t, _ = h.shape

    def adapted(x, name):
        return x @ f(w["w" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    x = rms_norm(h, f(w["attn_norm"]), eps)
    q = adapted(x, "q").reshape(b, t, n, dh)
    k = adapted(x, "k").reshape(b, t, kv, dh)
    v = adapted(x, "v").reshape(b, t, kv, dh)
    cos, sin, rot = rotary(spec, kind, t)
    cos, sin = f(cos)[:, None, :], f(sin)[:, None, :]
    q, k = rotate_half(q, cos, sin, rot), rotate_half(k, cos, sin, rot)
    k, v = (jnp.repeat(a, n // kv, axis=2) for a in (k, v))
    scores = jnp.einsum("bind,bjnd->bnij", q, k) / math.sqrt(dh)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = j <= i
    if kind == "sliding_attention" and variant.get("window", True):
        seen = seen & (i - j < spec["sliding_window"])
    scores = jnp.where(seen, scores, -jnp.inf)
    out = jnp.einsum("bnij,bjnd->bind", jax.nn.softmax(scores, -1), v)
    if variant.get("gate", True):
        out = out * jax.nn.sigmoid(x @ f(w["wgate"]))[..., None]
    h = h + adapted(out.reshape(b, t, n * dh), "o")

    x = rms_norm(h, f(w["mlp_norm"]), eps).reshape(b * t, -1)
    if at in spec["mlp_only_layers"]:
        return h + swiglu(x, *(f(w["dense"][name]) for name in (
            "w_gate", "w_up", "w_down"))).reshape(b, t, -1), None
    probs = jax.nn.softmax(x @ f(w["router"]), -1)
    top_k = spec["num_experts_per_tok"] - variant.get("fewer_experts", 0)
    top_p, top_i = jax.lax.top_k(probs, top_k)
    coef = (variant.get("scale", spec["moe_routed_scaling_factor"])
            * top_p / jnp.sum(top_p, -1, keepdims=True))

    def one_expert(total, item):
        e, w_gate, w_up, w_down = item
        mine = jnp.sum(jnp.where(top_i == spec["first_expert"] + e, coef,
                                 0.0), -1)
        return total + mine[:, None] * swiglu(x, f(w_gate), f(w_up),
                                              f(w_down)), None

    held = w["experts"]["w_gate"].shape[0]
    m, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                        (jnp.arange(held), w["experts"]["w_gate"],
                         w["experts"]["w_up"], w["experts"]["w_down"]))
    if variant.get("shared", True):
        m = m + swiglu(x, *(f(w["shared"][name]) for name in (
            "w_gate", "w_up", "w_down")))
    return h + m.reshape(b, t, -1), (top_i, probs)


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T], and the router's
    (experts [N, k], probabilities [N, E_all]) of every sparse layer.
    A layer at a time: each layer's backward recomputes that layer's own
    forward (`jax.checkpoint` around the layer, nothing inside it), which
    is what lets a peer's gradient at the published widths fit a 16 GB
    chip beside the program's frozen base (18.96 GB without)."""
    variant = variant or {}
    h = jnp.asarray(frozen["embed"], dtype)[tokens]          # [b, T, H]
    picks = []
    for at in range(len(spec["layer_types"])):
        def one(h, w, lora, at=at):
            return layer(spec, at, h, w, lora, dtype, variant)

        h, picked = jax.checkpoint(one)(h, frozen["layers"][at],
                                        adapters[at])
        if picked is not None:
            picks.append(picked)
    logits = rms_norm(h, jnp.asarray(frozen["final_norm"], dtype),
                      spec["rms_norm_eps"]) @ jnp.asarray(frozen["head"],
                                                          dtype)
    return logits, picks


def loss(spec, frozen, adapters, tokens, labels, dtype, variant=None):
    """Mean next-token cross-entropy over the held vocabulary."""
    logits, _ = forward(spec, frozen, adapters, tokens, dtype, variant)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def compiled(spec, dtype, variant=None):
    """(gradient, forward) as jitted functions of (frozen, w, tokens[,
    labels]): d loss / d w flat in `dtype`, and (logits, picks). The frozen
    tree is an ARGUMENT: closed over, its gigabytes would be constants of
    the program."""
    def of(flat, frozen, tokens, labels):
        return loss(spec, frozen, unflatten(spec, flat, dtype), tokens,
                    labels, dtype, variant)

    def gradient(frozen, w, tokens, labels):
        return jax.grad(of)(jnp.asarray(w, dtype), frozen, tokens, labels)

    def run(frozen, w, tokens):
        return forward(spec, frozen, unflatten(spec, w, dtype), tokens,
                       dtype, variant)

    return jax.jit(gradient), jax.jit(run)


def clipped_step(g, clip, eta):
    """-eta * clip_C(g), float64 numpy."""
    g = np.asarray(g, np.float64)
    return -eta * g * min(1.0, clip / max(np.linalg.norm(g), 1e-12))


def bf16(a):
    import ml_dtypes

    return np.asarray(a).astype(ml_dtypes.bfloat16).astype(np.float64)


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
