"""Model FLOPs and least bytes of Qwen3-Next-80B-A3B's round: what the
algorithm needs, from shapes alone, whatever implements it.

THE RULE (a gated delta-net layer's recurrence, S_t = exp(g_t) S_{t-1} (I -
beta_t k_t k_t^T) + beta_t k_t v_t^T, o_t = S_t^T q_t), counted in its
chunked form at chunks of L = 64 (`CHUNK`: the form the published kernels
run; the token-by-token recurrence needs about as many, 6 D E a token and
value head, and none of them is a matrix product). A window of T tokens, G
key heads of D, H value heads of E:

  inside a chunk   the (i, j <= i) pairs, T (L + 1) / 2 of them: k_i . k_j
                   and q_i . k_j once a KEY head (2 D each), the scores
                   times the deltas a value head (2 E), and the forward
                   substitution of (I + A) [U | W] = rhs, T (L - 1) / 2
                   pairs of 2 (E + D) a value head
  the state        W S, K^T D and q S: 2 D E each, a token and value head

so forward = T (L + 1) / 2 x (4 G D + 2 H E) + T (L - 1) / 2 x 2 H (E + D)
+ 6 T H D E. Every product is bilinear in activations (there is no weight),
so the backward is twice the forward: a step is 3 x. Exponentials,
cumulative sums, the l2 norms and the elementwise products are not
counted; nor is recomputation the PROGRAM chooses (`jax.checkpoint` around
the layer).

Its least bytes: every input (q, k [T, G D], v [T, H E], g, beta [T, H])
read once and o [T, H E] written once, 4 bytes each (the scope's interface
is float32); the backward reads the inputs and o's cotangent and writes the
inputs' cotangents.

THE ROUND's model FLOPs, for the share of the whole step's peak: every
product with a frozen weight (2 x in x out a token: W_qkvz, W_ba, W_out;
q, k, v, o; the router, the shared expert and its gate, the head; the
routed experts at the share of a token's `num_experts_per_tok` that a
uniform router sends to the experts HELD here), the adapters' (2 r (in +
out)), the rule as above and the attention core's causal pairs (2 d + 2 d
a pair and head). A sampled window pays forward and ACTIVATION backward
(the base is frozen: no weight gradient; x 2), the adapters' B besides (2
r out), the rule and the attention core x 3; a held-out window the forward
alone.
"""

CHUNK = 64


def rule_forward_flops(windows, tokens, key_heads, key_dim, value_heads,
                       value_dim):
    size = min(CHUNK, tokens)
    below = tokens * (size + 1) // 2
    return int(windows) * (
        below * (4 * key_heads * key_dim + 2 * value_heads * value_dim)
        + tokens * (size - 1) // 2 * 2 * value_heads * (value_dim + key_dim)
        + 6 * tokens * value_heads * key_dim * value_dim)


def rule_step_flops(windows, *shape):
    """Forward and backward (twice the forward) of `windows` windows."""
    return 3 * rule_forward_flops(windows, *shape)


def _rule_inputs(key_heads, key_dim, value_heads, value_dim):
    return 2 * key_heads * key_dim + value_heads * value_dim \
        + 2 * value_heads


def rule_forward_bytes(windows, tokens, key_heads, key_dim, value_heads,
                       value_dim):
    ins = _rule_inputs(key_heads, key_dim, value_heads, value_dim)
    return 4 * int(windows) * tokens * (ins + value_heads * value_dim)


def rule_step_bytes(windows, tokens, key_heads, key_dim, value_heads,
                    value_dim):
    """Forward, and the backward's reads (inputs, o's cotangent) and
    writes (the inputs' cotangents)."""
    ins = _rule_inputs(key_heads, key_dim, value_heads, value_dim)
    return (rule_forward_bytes(windows, tokens, key_heads, key_dim,
                               value_heads, value_dim)
            + 4 * int(windows) * tokens * (2 * ins
                                           + value_heads * value_dim))


def rule_shape(config):
    """(tokens, key heads, key dim, value heads, value dim) of the
    configuration's file."""
    return (config["model"]["window_tokens"],
            config["linear_num_key_heads"], config["linear_key_head_dim"],
            config["linear_num_value_heads"],
            config["linear_value_head_dim"])


def rule_layers(config):
    interval = config["full_attention_interval"]
    return sum((at + 1) % interval != 0
               for at in range(config["num_hidden_layers"]))


def rule_round(config, sampled, held_out):
    """(model FLOPs, least bytes) of the rule in one round on `sampled`
    stepped and `held_out` evaluated windows, all its layers."""
    shape, layers = rule_shape(config), rule_layers(config)
    return (layers * (rule_step_flops(sampled, *shape)
                      + rule_forward_flops(held_out, *shape)),
            layers * (rule_step_bytes(sampled, *shape)
                      + rule_forward_bytes(held_out, *shape)))


def round_model_flops(config, sampled, held_out):
    """Model FLOPs of one round on `sampled` stepped and `held_out`
    evaluated windows (module doc)."""
    t, key_heads, dk, value_heads, dv = rule_shape(config)
    hidden, r = config["hidden_size"], config["adapters"]["rank"]
    keys, values = key_heads * dk, value_heads * dv
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = config["head_dim"]
    width = config["moe_intermediate_size"]
    held = config["num_experts"] / config["published"]["num_experts"]
    sparse = (2 * hidden * config["published"]["num_experts"]     # router
              + 6 * hidden * config["shared_expert_intermediate_size"]
              + 2 * hidden                                        # its gate
              + config["num_experts_per_tok"] * held * 6 * hidden * width)
    adapted = {"gdn": [(hidden, 2 * keys + 2 * values), (values, hidden)],
               "attention": [(hidden, 2 * heads * dh), (hidden, kv * dh),
                             (hidden, kv * dh), (heads * dh, hidden)]}
    plain = {"gdn": 2 * hidden * 2 * value_heads, "attention": 0}  # W_ba
    forward = step = 0
    for at in range(config["num_hidden_layers"]):
        kind = "attention" if (at + 1) % config["full_attention_interval"] \
            == 0 else "gdn"
        frozen = sparse + plain[kind] + sum(2 * i * o
                                            for i, o in adapted[kind])
        lora = sum(2 * r * (i + o) for i, o in adapted[kind])
        grad_b = sum(2 * r * o for _, o in adapted[kind])
        if kind == "gdn":
            core = rule_forward_flops(1, t, key_heads, dk, value_heads,
                                      dv) / t
        else:
            core = heads * (t + 1) / 2 * 4 * dh
        forward += frozen + lora + core
        step += 2 * (frozen + lora) + grad_b + 3 * core
    head = 2 * hidden * config["vocab_size"]
    return int(t * (sampled * (step + 2 * head)
                    + held_out * (forward + head)))
