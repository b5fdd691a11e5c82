"""Model FLOPs and least bytes of Olmo-Hybrid-7B's round: what the
algorithm needs, from shapes alone, whatever implements it.

THE RULE is `benchmark/flops/qwen3_next.py`'s count (its module doc: the
chunked form at chunks of 64, a step 3 x the forward, the least bytes its
inputs and its result once each), taken at the MODEL's widths: 30 key
heads of 96 serving 30 value heads of 192, a rectangular state, whatever
the kernel pads its heads to (the zero columns' products are the
program's, as recomputation is, and count as nothing here).

THE ROUND's model FLOPs, for the share of the whole step's peak: every
product with a frozen weight (2 x in x out a token: W_qkvz, W_ba, W_out;
q, k, v, o; the SwiGLU's three; the head), the adapters' (2 r (in + out)),
the rule as above and the attention core's causal pairs (2 d + 2 d a pair
and head). A sampled window pays forward and ACTIVATION backward (the base
is frozen: no weight gradient; x 2), the adapters' B besides (2 r out),
the rule and the attention core x 3; a held-out window the forward alone.
"""

from benchmark.flops.qwen3_next import (rule_forward_bytes,
                                        rule_forward_flops, rule_shape,
                                        rule_step_bytes, rule_step_flops)


def kinds(config):
    return config["layer_types"][:config["num_hidden_layers"]]


def rule_layers(config):
    return kinds(config).count("linear_attention")


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
    dh = hidden // heads
    mlp = 3 * 2 * hidden * config["intermediate_size"]
    adapted = {"linear_attention": [(hidden, 2 * keys + 2 * values),
                                    (values, hidden)],
               "full_attention": [(hidden, hidden), (hidden, kv * dh),
                                  (hidden, kv * dh), (hidden, hidden)]}
    plain = {"linear_attention": 2 * hidden * 2 * value_heads,  # W_ba
             "full_attention": 0}
    forward = step = 0
    for kind in kinds(config):
        frozen = mlp + plain[kind] + sum(2 * i * o for i, o in adapted[kind])
        lora = sum(2 * r * (i + o) for i, o in adapted[kind])
        grad_b = sum(2 * r * o for _, o in adapted[kind])
        if kind == "linear_attention":
            core = rule_forward_flops(1, t, key_heads, dk, value_heads,
                                      dv) / t
        else:
            core = heads * (t + 1) / 2 * 4 * dh
        forward += frozen + lora + core
        step += 2 * (frozen + lora) + grad_b + 3 * core
    head = 2 * hidden * config["vocab_size"]
    return int(t * (sampled * (step + 2 * head)
                    + held_out * (forward + head)))
