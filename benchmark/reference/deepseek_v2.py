"""Plain reference of DeepSeek-V2's decoder share with rank-r adapters, and
of one Biscotti round on it: forward, next-token loss, the adapters'
gradient, the clipped step, the DP noise, Krum, the sum, the ledger.

Written from the published `config.json`
(https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json) and
the equations of ISSUE 31; imports nothing of biscotti_tpu. Straight
`jax.numpy` in ONE dtype (float64 in the CPU tests; float32 under
`jax.default_matmul_precision("highest")` on the chip): no kernels, no
sort and no grouped product. So that a peer's gradient at the published
widths fits a 16 GB chip beside the program's 10.3 GB base, it runs a peer
at a time, a layer at a time (`jax.checkpoint` around each layer), the
attention's scores HEAD_BLOCK heads at a time and the experts one at a
time (every held expert computes every token; the token's coefficient for
it, zero where it was not chosen, weighs the result).

The rotary embedding is written as DeepSeek's own code has it: the rope
dimensions de-interleaved (x[0::2] then x[1::2]), then rotate-half, the
frequencies by `yarn_find_correction_range` and `yarn_linear_ramp_mask`.

The weights and the shards are INPUTS, the same arrays the program holds:

  spec      the published keys (hidden_size, num_attention_heads,
            q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
            v_head_dim, num_hidden_layers, first_k_dense_replace, n_group,
            topk_group, num_experts_per_tok, routed_scaling_factor,
            norm_topk_prob, rope_theta, rope_scaling, rms_norm_eps) cut to
            the layers held, plus `first_expert`, `lora_rank`, `lora_alpha`
  frozen    embed [V, H], head [H, V], final_norm [H], layers[l]: attn_norm,
            mlp_norm, q_norm, kv_norm, w_qa, w_qb, w_kva, w_kvb, w_o, lora_a
            {qa, qb, kva, kvb, o}, and `dense` {w_gate, w_up, w_down} or
            router [H, E_all], `shared`, `experts` {w_gate [E, H, F], w_up,
            w_down [E, F, H]}: the E experts first_expert .. + E - 1
  w         the wire vector: the adapters' B [r, out], layer by layer and
            within a layer in the order kva, kvb, o, qa, qb (the ravel of
            {"layers": [{"kva", "kvb", "o", "qa", "qb"}]}), float

`variant` names a departure, for the controls that must come out not
correct: {"fewer_experts": 1} (five a token where the model takes six),
{"groups": False} (no group limit), {"renormalise": True}, {"scale": 1.0}
(the 16 left out), {"shared_rope": False} (the shared rotary key left out
of the scores), {"inner_norms": False}, {"mscale": False} (m^2 left out
of the softmax scale), {"shared": False} (the shared experts left out).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from .laguna import bf16, clipped_step  # noqa: F401  (plain numpy helpers)

ADAPTED = ("kva", "kvb", "o", "qa", "qb")  # the wire vector's order
HEAD_BLOCK = 16  # heads whose scores are held at once


def widths(spec):
    """{projection: (in, out)}."""
    n, hidden = spec["num_attention_heads"], spec["hidden_size"]
    nope, rope = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    return {"qa": (hidden, spec["q_lora_rank"]),
            "qb": (spec["q_lora_rank"], n * (nope + rope)),
            "kva": (hidden, spec["kv_lora_rank"] + rope),
            "kvb": (spec["kv_lora_rank"], n * (nope + spec["v_head_dim"])),
            "o": (n * spec["v_head_dim"], hidden)}


def layout(spec):
    """[(name, shape)] of the wire vector's leaves, in order."""
    out_of = widths(spec)
    return [(f"layers[{at}].{name}", (spec["lora_rank"], out_of[name][1]))
            for at in range(spec["num_hidden_layers"]) for name in ADAPTED]


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
    """[{"kva", "kvb", "o", "qa", "qb"}: B [r, out]] layer by layer."""
    per_layer = [{} for _ in range(spec["num_hidden_layers"])]
    for (name, shape), (_, piece) in zip(layout(spec),
                                         leaves(spec, jnp.asarray(flat))):
        at = int(name[len("layers["):name.index("]")])
        per_layer[at][name.split(".")[1]] = piece.reshape(shape).astype(dtype)
    return per_layer


def yarn_mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def rotary(spec, length):
    """(cos, sin) [T, rope] in float64 numpy, each frequency twice (the
    rotate-half form), as DeepseekV2YarnRotaryEmbedding makes them."""
    dim, base = spec["qk_rope_head_dim"], float(spec["rope_theta"])
    scaling = spec["rope_scaling"]
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * (1.0 - mask) + extra * mask
    freqs = np.outer(np.arange(length), inv)
    emb = np.concatenate([freqs, freqs], axis=-1)
    m = (yarn_mscale(factor, scaling["mscale"])
         / yarn_mscale(factor, scaling["mscale_all_dim"]))
    return np.cos(emb) * m, np.sin(emb) * m


def apply_rotary(x, cos, sin):
    """x [..., T, heads, rope]; cos, sin [T, 1, rope]."""
    d = x.shape[-1]
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1, -2)
    x = x.reshape(x.shape[:-2] + (d,))           # x[0::2] then x[1::2]
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + turned * sin


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def layer(spec, at, h, w, lora, dtype, variant):
    """Layer `at` on h [b, T, H] with its frozen weights `w` and adapters
    `lora`: (h', the router's (experts [N, k], probabilities [N, E_all]),
    None on a dense layer)."""
    f = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    n = spec["num_attention_heads"]
    nope, rope = spec["qk_nope_head_dim"], spec["qk_rope_head_dim"]
    dv, kv_rank = spec["v_head_dim"], spec["kv_lora_rank"]
    scale_lora = spec["lora_alpha"] / spec["lora_rank"]
    eps = spec["rms_norm_eps"]
    b, t, _ = h.shape

    def adapted(x, name):
        return x @ f(w["w_" + name]) + scale_lora * (
            (x @ f(w["lora_a"][name])) @ lora[name])

    def inner(x, name):
        return rms_norm(x, f(w[name]), eps) \
            if variant.get("inner_norms", True) else x

    x = rms_norm(h, f(w["attn_norm"]), eps)
    q = adapted(inner(adapted(x, "qa"), "q_norm"), "qb")
    q = q.reshape(b, t, n, nope + rope)
    latent = adapted(x, "kva")
    k_r = latent[..., kv_rank:].reshape(b, t, 1, rope)
    kv = adapted(inner(latent[..., :kv_rank], "kv_norm"), "kvb")
    kv = kv.reshape(b, t, n, nope + dv)
    cos, sin = rotary(spec, t)
    cos, sin = f(cos)[:, None, :], f(sin)[:, None, :]
    q_r = apply_rotary(q[..., nope:], cos, sin)
    k_r = apply_rotary(k_r, cos, sin)
    if not variant.get("shared_rope", True):
        k_r = jnp.zeros_like(k_r)
    q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, (b, t, n, rope))], axis=-1)
    v = kv[..., nope:]
    scaling = spec["rope_scaling"]
    m = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) \
        if variant.get("mscale", True) else 1.0
    softmax_scale = (nope + rope) ** -0.5 * m * m
    seen = np.arange(t)[None, :] <= np.arange(t)[:, None]

    @jax.checkpoint
    def some_heads(q, k, v):  # [b, T, heads of the block, .]
        scores = jnp.einsum("bind,bjnd->bnij", q, k) * softmax_scale
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bnij,bjnd->bind", jax.nn.softmax(scores, -1), v)

    step = min(HEAD_BLOCK, n)
    out = jnp.concatenate([
        some_heads(q[:, :, i:i + step], k[:, :, i:i + step],
                   v[:, :, i:i + step]) for i in range(0, n, step)], axis=2)
    h = h + adapted(out.reshape(b, t, n * dv), "o")

    x = rms_norm(h, f(w["mlp_norm"]), eps).reshape(b * t, -1)
    if at < spec["first_k_dense_replace"]:
        return h + swiglu(x, *(f(w["dense"][name]) for name in (
            "w_gate", "w_up", "w_down"))).reshape(b, t, -1), None
    probs = jax.nn.softmax(x @ f(w["router"]), -1)
    e_all, groups = probs.shape[-1], spec["n_group"]
    eligible = probs
    if groups > 1 and variant.get("groups", True):
        # group_limited_greedy: the topk_group groups with the largest
        # maximum; the others' scores count as 0
        best = probs.reshape(-1, groups, e_all // groups).max(-1)
        _, kept = jax.lax.top_k(best, spec["topk_group"])
        group_mask = jnp.zeros_like(best).at[
            jnp.arange(best.shape[0])[:, None], kept].set(1.0)
        eligible = jnp.where(jnp.repeat(group_mask, e_all // groups, axis=1)
                             > 0, probs, 0.0)
    top_k = spec["num_experts_per_tok"] - variant.get("fewer_experts", 0)
    top_p, top_i = jax.lax.top_k(eligible, top_k)
    scale = variant.get("scale", spec["routed_scaling_factor"])
    if variant.get("renormalise", spec["norm_topk_prob"]):
        coef = scale * top_p / jnp.sum(top_p, -1, keepdims=True)
    else:
        coef = scale * top_p

    def one_expert(total, item):
        e, w_gate, w_up, w_down = item
        mine = jnp.sum(jnp.where(top_i == spec["first_expert"] + e, coef,
                                 0.0), -1)
        return total + mine[:, None] * swiglu(x, f(w_gate), f(w_up),
                                              f(w_down)), None

    held = w["experts"]["w_gate"].shape[0]
    moe, _ = jax.lax.scan(jax.checkpoint(one_expert), jnp.zeros_like(x),
                          (jnp.arange(held), w["experts"]["w_gate"],
                           w["experts"]["w_up"], w["experts"]["w_down"]))
    if variant.get("shared", True):
        moe = moe + swiglu(x, *(f(w["shared"][name]) for name in (
            "w_gate", "w_up", "w_down")))
    return h + moe.reshape(b, t, -1), (top_i, probs)


def forward(spec, frozen, adapters, tokens, dtype, variant=None):
    """logits [b, T, V] of `tokens` int[b, T], and the router's
    (experts [N, k], probabilities [N, E_all]) of every sparse layer; a
    layer at a time."""
    variant = variant or {}
    h = jnp.asarray(frozen["embed"][tokens], dtype)          # [b, T, H]
    picks = []
    for at in range(spec["num_hidden_layers"]):
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


_COMPILED = {}


def compiled(spec, dtype, variant=None):
    """(gradient, forward) as jitted functions of (frozen, w, tokens[,
    labels]): d loss / d w flat in `dtype`, and (logits, picks). The frozen
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
    logits, _ = run(frozen, w_next, jnp.asarray(x_val))
    err = float(jnp.mean(jnp.argmax(logits, -1) != jnp.asarray(y_val)))
    return {"sampled": cidx, "deltas": deltas, "scores": scores,
            "accept": accept, "agg": agg, "w_next": w_next,
            "stake_next": stake_next, "err": err}
