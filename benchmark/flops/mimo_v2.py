"""Model FLOPs and least bytes of MiMo-V2.5's round: what the algorithm
needs, from shapes alone, whatever implements it.

THE ATTENTION CORE of a kind of layer (window: query i sees key j where 0
<= i - j < 128, and its head's sink; full: every j <= i), counted over the
(query, key) pairs a query SEES, so the count does not change with the
blocks a kernel visits: a window of T tokens has `pairs(T, window)` = window
T - window (window - 1) / 2 of them a head (T (T + 1) / 2 under the causal
mask). A pair costs q . k (2 x 192) and p v (2 x 128) forward; the backward
is four such products (dv, dp, dq, dk): twice the forward, so a stepped
window is 3 x. The exponentials, the running maximum and sum, the sink's
one column and the mask are not counted; nor is recomputation the PROGRAM
chooses (`jax.checkpoint` around the layer, the scores again in the
backward kernel), nor the pairs of a visited block that the mask hides.

Its least bytes, at the core's own interface: q [64, T, 192], k [kv, T,
192], v [kv, T, 128] read once in bfloat16 and the result [64, T, 128]
written once in float32; the backward reads the three again, the result
and its cotangent (float32), and writes the three cotangents (bfloat16).
The sinks and the rows' statistics are not counted (64 and 64 T floats).

THE ROUND's model FLOPs, for the share of the whole step's peak: every
product with a frozen weight (2 x in x out a token: the fused W_qkv and
W_o, the router, layer 0's dense SwiGLU, the head; the routed experts at
the share of a token's `num_experts_per_tok` that a uniform router sends to
the experts HELD here), the adapters' (2 r (in + out)) and the core as
above. A sampled window pays forward and ACTIVATION backward (the base is
frozen: no weight gradient; x 2), the adapters' B besides (2 r out) and the
core x 3; a held-out window the forward alone.
"""

KINDS = ("full", "window")  # hybrid_layer_pattern's 0 and 1


def pairs(tokens, window):
    """(query, key) pairs a head's queries see in a window of `tokens`."""
    window = min(window, tokens)
    return window * tokens - window * (window - 1) // 2


def kind_shape(config, kind):
    """(tokens, what a query sees back, query heads, key/value heads, q's
    and k's width, v's) of a layer of `kind`."""
    tokens = config["model"]["window_tokens"]
    return (tokens,
            config["sliding_window"] if kind == "window" else tokens,
            config["num_attention_heads"],
            config["swa_num_key_value_heads" if kind == "window"
                   else "num_key_value_heads"],
            config["head_dim"], config["v_head_dim"])


def core_forward_flops(windows, tokens, window, heads, kv, d, e):
    return int(windows) * heads * pairs(tokens, window) * (2 * d + 2 * e)


def core_step_flops(windows, *shape):
    """Forward and backward (twice the forward) of `windows` windows."""
    return 3 * core_forward_flops(windows, *shape)


def _operands(tokens, heads, kv, d, e):
    return tokens * (heads * d + kv * (d + e))


def core_forward_bytes(windows, tokens, window, heads, kv, d, e):
    return int(windows) * (2 * _operands(tokens, heads, kv, d, e)
                           + 4 * tokens * heads * e)


def core_step_bytes(windows, tokens, window, heads, kv, d, e):
    """Forward, and the backward's reads (the operands, the result and its
    cotangent) and writes (the operands' cotangents)."""
    return (core_forward_bytes(windows, tokens, window, heads, kv, d, e)
            + int(windows) * (4 * _operands(tokens, heads, kv, d, e)
                              + 2 * 4 * tokens * heads * e))


def layers_of(config, kind):
    held = config["hybrid_layer_pattern"][:config["num_hidden_layers"]]
    return sum(KINDS[at] == kind for at in held)


def core_round(config, kind, sampled, held_out):
    """(model FLOPs, least bytes) of the cores of `kind` in one round on
    `sampled` stepped and `held_out` evaluated windows, all its layers."""
    shape, layers = kind_shape(config, kind), layers_of(config, kind)
    return (layers * (core_step_flops(sampled, *shape)
                      + core_forward_flops(held_out, *shape)),
            layers * (core_step_bytes(sampled, *shape)
                      + core_forward_bytes(held_out, *shape)))


def round_model_flops(config, sampled, held_out):
    """Model FLOPs of one round on `sampled` stepped and `held_out`
    evaluated windows (module doc)."""
    hidden, r = config["hidden_size"], config["adapters"]["rank"]
    heads, d, e = (config["num_attention_heads"], config["head_dim"],
                   config["v_head_dim"])
    t = config["model"]["window_tokens"]
    held = config["n_routed_experts"] / config["published"][
        "n_routed_experts"]
    sparse = (2 * hidden * config["published"]["n_routed_experts"]  # router
              + config["num_experts_per_tok"] * held * 6 * hidden
              * config["moe_intermediate_size"])
    dense = 6 * hidden * config["intermediate_size"]
    forward = step = 0
    for at in range(config["num_hidden_layers"]):
        kind = KINDS[config["hybrid_layer_pattern"][at]]
        kv = kind_shape(config, kind)[3]
        adapted = [(hidden, (heads + kv) * d + kv * e), (heads * e, hidden)]
        frozen = (sparse if config["moe_layer_freq"][at] else dense) \
            + sum(2 * i * o for i, o in adapted)
        lora = sum(2 * r * (i + o) for i, o in adapted)
        grad_b = sum(2 * r * o for _, o in adapted)
        core = core_forward_flops(1, *kind_shape(config, kind)) / t
        forward += frozen + lora + core
        step += 2 * (frozen + lora) + grad_b + 3 * core
    head = 2 * hidden * config["vocab_size"]
    return int(t * (sampled * (step + 2 * head)
                    + held_out * (forward + head)))
