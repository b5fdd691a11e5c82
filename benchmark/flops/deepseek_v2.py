"""Model FLOPs of DeepSeek-V2's attention core `softmax(s q k^T + causal)
v`: what the algorithm needs, from shapes alone, whatever implements it.

A head of a window of T tokens has T (T + 1) / 2 (query, key) pairs the
causal mask lets through. A pair costs the score's product over the score
width d (2 d FLOP: a multiply-add a dimension) and the value's over the
value width e (2 e): 2 d + 2 e forward. Training the adapters needs the
backward with respect to q, k and v: the probabilities' cotangent (2 e),
dv (2 e), dq and dk (2 d each), 4 d + 4 e = 2 x the forward by products;
with the forward's recomputation inside a fused backward (the scores
again, 2 d) the usual count is 2.5 x. Recomputation the PROGRAM chooses
(`jax.checkpoint` around the layer) is not counted: it is the program's
choice, not the algorithm's need. Exponentials, maxima and sums are not
counted either.
"""


def core_forward_flops(windows, heads, tokens, score_width, value_width):
    pairs = tokens * (tokens + 1) // 2
    return int(windows) * heads * pairs * (2 * score_width + 2 * value_width)


def core_step_flops(windows, heads, tokens, score_width, value_width):
    """Forward and backward (2.5 x the forward) of `windows` windows."""
    return 7 * core_forward_flops(windows, heads, tokens, score_width,
                                  value_width) // 2
