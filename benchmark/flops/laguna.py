"""Model FLOPs of Laguna's routed experts: what the algorithm needs, from
shapes and from the assignments a round really made.

One token-expert assignment runs one SwiGLU of width F on a hidden state
of width H: three products (gate, up: [H] x [H, F]; down: [F] x [F, H]),
2 H F multiply-adds each, so 6 H F FLOP forward. Training the adapters
behind frozen experts needs the activations' backward only (the experts'
weights get no gradient): the three transposed products, another 6 H F.
Recomputation (the layer is rematerialised in the backward pass) is not
counted: it is the program's choice, not the algorithm's need.
"""


def expert_forward_flops(assignments, hidden, width):
    return 6 * int(assignments) * hidden * width


def expert_step_flops(assignments, hidden, width):
    """Forward and activation backward of `assignments` token-expert
    assignments."""
    return 2 * expert_forward_flops(assignments, hidden, width)
