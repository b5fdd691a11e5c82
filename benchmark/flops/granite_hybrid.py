"""Model FLOPs and least bytes of Granite-4.0-H-Micro's round: what the
algorithm needs, from shapes alone, whatever implements it.

THE SCAN (a Mamba-2 layer's recurrence, `y_t = S_t C_t + D x_t`), counted in
its chunked form at the PUBLISHED chunk L (`mamba_chunk_size`: the form the
architecture is stated for; the token-by-token recurrence needs about as
many, 5 H P N a token, and none of them is a matrix product). A window of T
tokens, H heads of P, one group of state N:

  inside a chunk   the (query, key) pairs the causal mask lets through, T (L
                   + 1) / 2 of them: C_i . B_j once for all heads (2 N), and
                   a head's score times dt x_j (2 P a head)
  a chunk's state  sum_j decay dt_j x_j (x) B_j: 2 N a token and channel
  from before      S_prev C_i: 2 N a token and channel

so forward = T (L + 1) / 2 x (2 N + 2 H P) + 4 T N H P. Every product is
bilinear in activations (there is no weight), so the backward is twice the
forward: a step is 3 x. Exponentials, cumulative sums, the D term and the
elementwise products are not counted; nor is recomputation the PROGRAM
chooses (`jax.checkpoint` around the layer).

Its least bytes: every input (x [T, H P], B, C [T, N], dt [T, H]) read once
and y [T, H P] written once, 4 bytes each (the scope's interface is float32);
the backward reads the inputs and y's cotangent and writes the inputs'.

THE ROUND's model FLOPs, for the share of the whole step's peak: every
product with a frozen weight (2 x in x out a token: in_proj, out_proj, q, k,
v, o, the SwiGLU's three, the tied head), the adapters' (2 r (in + out)),
the scan as above and the attention core's causal pairs (2 d + 2 d a pair
and head). A sampled window pays forward and ACTIVATION backward (the base
is frozen: no weight gradient; x 2), the adapters' B besides (2 r out), the
scan and the attention core x 3; a held-out window the forward alone.
"""


def scan_forward_flops(windows, tokens, heads, head_dim, state, chunk):
    size = min(chunk, tokens)
    pairs = tokens * (size + 1) // 2
    channels = heads * head_dim
    return int(windows) * (pairs * (2 * state + 2 * channels)
                           + 4 * tokens * state * channels)


def scan_step_flops(windows, tokens, heads, head_dim, state, chunk):
    """Forward and backward (twice the forward) of `windows` windows."""
    return 3 * scan_forward_flops(windows, tokens, heads, head_dim, state,
                                  chunk)


def scan_forward_bytes(windows, tokens, heads, head_dim, state):
    ins = heads * head_dim + 2 * state + heads
    return 4 * int(windows) * tokens * (ins + heads * head_dim)


def scan_step_bytes(windows, tokens, heads, head_dim, state):
    """Forward, and the backward's reads (inputs, y's cotangent) and
    writes (the inputs' cotangents)."""
    ins = heads * head_dim + 2 * state + heads
    return (scan_forward_bytes(windows, tokens, heads, head_dim, state)
            + 4 * int(windows) * tokens * (2 * ins + heads * head_dim))


def scan_shape(config):
    """(tokens, heads, head_dim, state) of the configuration's file."""
    return (config["model"]["window_tokens"], config["mamba_n_heads"],
            config["mamba_d_head"], config["mamba_d_state"])


def round_model_flops(config, sampled, held_out):
    """Model FLOPs of one round on `sampled` stepped and `held_out`
    evaluated windows (module doc)."""
    t, ssm_heads, ssm_dim, state = scan_shape(config)
    hidden, r = config["hidden_size"], config["adapters"]["rank"]
    inner = ssm_heads * ssm_dim
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    dh = hidden // heads
    mlp = 3 * 2 * hidden * config["shared_intermediate_size"]
    adapted = {"mamba": [(hidden, 2 * inner + 2 * state + ssm_heads),
                         (inner, hidden)],
               "attention": [(hidden, hidden), (hidden, kv * dh),
                             (hidden, kv * dh), (hidden, hidden)]}
    forward = step = 0
    for kind in config["layer_types"]:
        frozen = mlp + sum(2 * i * o for i, o in adapted[kind])
        lora = sum(2 * r * (i + o) for i, o in adapted[kind])
        grad_b = sum(2 * r * o for _, o in adapted[kind])
        if kind == "mamba":
            core = scan_forward_flops(1, t, ssm_heads, ssm_dim, state,
                                      config["mamba_chunk_size"]) / t
        else:
            core = heads * (t + 1) / 2 * 4 * dh
        forward += frozen + lora + core
        step += 2 * (frozen + lora) + grad_b + 3 * core
    head = 2 * hidden * config["vocab_size"]
    return int(t * (sampled * (step + 2 * head)
                    + held_out * (forward + head)))
