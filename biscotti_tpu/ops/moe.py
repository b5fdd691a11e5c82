"""A sparse-expert layer that is told which experts it holds.

The router keeps its published width (it scores ALL the model's experts),
its experts a token and its rule (`route`: plain top-k of a softmax, or
group-limited greedy, or the top-k of sigmoid scores plus a choice bias;
the chosen probabilities renormalised or not); this device holds the
contiguous slice `[first, first + E)` of them (the `E` leading rows of the
stacked expert weights) and computes ITS experts' part of the layer's
result for the tokens routed to them. What the absent experts would add is
left out, as on one chip of an expert-parallel deployment before the
exchange; no code here stands in for the other chips (tests/test_laguna.py:
the shares add up to the uncut layer).

No token is dropped and there is no capacity factor: the token-expert
assignments are sorted by expert (those of experts held elsewhere last) and
ONE grouped product a weight runs over the held rows, the groups' sizes the
held experts' loads: the rows past them are in no group, cost no product
and come out zeros. The sorted buffer is cut to CAPACITY times the rows a
uniform router would send here, and a call whose held rows pass that runs
the same code on the uncut buffer instead (`lax.cond`): slower, never lossy
(`counts["uncut"]` says which ran). The choice is made where no residual of
a differentiated program crosses it (`_routed`, a `custom_vjp` in the rows
and their coefficients): the forward chooses outside any differentiation
and keeps only its own inputs, the backward chooses again and runs the
chosen side's forward and transpose inside ONE branch, handing out the two
cotangents and nothing else. A `lax.cond` that `jax.grad` splits hands the
frozen expert stacks out of its branches as residuals instead, and the
compiled round holds a copy of every stack (7.03 GB at DeepSeek-V2's sizes;
PERF.md section 6, PRs 31 and 32).

The product's time follows the (group, row tile) pairs it visits, not its
rows (PERF.md section 6, PR 28: the compiler's `ragged-dot` walks row tiles
of 512, and 64 groups of 120 rows cost it 93 visits, six times the rows).
So where the shapes allow (`_plan`: hidden size and expert width multiples
of 128, both buffers whole row tiles, bfloat16 or float32 weights) the
product is ops/grouped_matmul.py's kernel at the smallest row tile that
holds the rows a uniform router sends a group; elsewhere (the tiny model of
the CPU tests) the compiler's `ragged_dot`. One algorithm, one parameter
read off the shapes: `counts` says which side ran and how full the visited
tiles were.

Rows go to their sorted places and come back by gathers in both directions
(`_dispatch`, `_combine`, each the other's transpose): the transpose XLA
would derive for a gather is a scatter-add.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from biscotti_tpu.ops import grouped_matmul


def route(x: jax.Array, router_w: jax.Array, top_k: int, scale: float,
          groups: int = 1, groups_kept: int = 1, renormalise: bool = True,
          bias: jax.Array = None):
    """softmax over ALL experts, the `top_k` largest, their probabilities
    scaled: (experts int32[N, k], coefficients float32[N, k], probabilities
    float32[N, E_all]).

    `groups` > 1 is the group-limited greedy choice (DeepSeek-V2's
    `topk_method`): the experts in `groups` equal runs, the `groups_kept`
    runs with the largest maximum probability, and the `top_k` largest of
    the experts in those (the others' probabilities count as 0). One group
    is the plain top-k. `renormalise` divides the chosen probabilities by
    their sum before the scale (`norm_topk_prob`); without it the
    coefficients are `scale * p`.

    With `bias` float[E_all] (MiMo-V2's `scoring_func` sigmoid under
    `topk_method` noaux_tc) an expert's score is s = sigmoid(logit), each
    on its own, the `top_k` are the largest of s + bias and are weighed by
    s alone; what comes back third is then s + bias, what the choice was
    made by. One group only."""
    logits = jnp.dot(x.astype(router_w.dtype), router_w,
                     preferred_element_type=jnp.float32)
    if bias is not None:
        if groups > 1:
            raise ValueError("a choice bias goes with one group of experts")
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores + bias.astype(jnp.float32)
        _, top_i = jax.lax.top_k(chosen_by, top_k)
        top_p = jnp.take_along_axis(scores, top_i, axis=-1)
        coef = scale * (top_p / jnp.sum(top_p, axis=-1, keepdims=True)
                        if renormalise else top_p)
        return top_i.astype(jnp.int32), coef, chosen_by
    probs = jax.nn.softmax(logits, axis=-1)
    eligible = probs
    if groups > 1:
        n, e = probs.shape
        best = jnp.max(probs.reshape(n, groups, e // groups), axis=-1)
        _, kept = jax.lax.top_k(best, groups_kept)            # [N, kept]
        of = jnp.arange(e, dtype=jnp.int32) // (e // groups)  # [E]'s group
        inside = jnp.any(of[None, :, None] == kept[:, None, :], axis=-1)
        eligible = jnp.where(inside, probs, 0.0)
    top_p, top_i = jax.lax.top_k(eligible, top_k)
    if renormalise:
        coef = scale * top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        coef = scale * top_p
    return top_i.astype(jnp.int32), coef, probs


CAPACITY = 2.0  # x the uniform router's expectation, before the uncut path


def _gather_sum(rows, where, weight):
    """out[n] = sum_j weight[n, j] * rows[where[n, j]]; `where` == len(rows)
    reads a zero row. rows [C, H]; where, weight [N, k]."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    return jnp.einsum("nk,nkh->nh", weight.astype(rows.dtype), padded[where],
                      preferred_element_type=rows.dtype)


@jax.custom_vjp
def _dispatch(x, token, where, valid):
    """x[token]: the sorted rows' hidden states, [C, H]. `where` [N, k] is
    each assignment's sorted row (C where it has none here) and `valid`
    marks those that have one: the transpose's map."""
    return x[token]


def _dispatch_fwd(x, token, where, valid):
    return x[token], (where, valid)


def _dispatch_bwd(res, g):
    where, valid = res
    return _gather_sum(g, where, valid), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(rows, weight, token, slot, where):
    """out[n] = sum over n's assignments j of weight[n, j] * rows[where[n,
    j]], float32[N, H]: the sorted rows back at their tokens, weighed.
    `token`, `slot` [C]: the assignment each sorted row is."""
    return _gather_sum(rows, where, weight)


def _combine_fwd(rows, weight, token, slot, where):
    return _gather_sum(rows, where, weight), (rows, weight, token, slot,
                                              where)


def _combine_bwd(res, g):
    rows, weight, token, slot, where = res
    back = g[token]                                   # [C, H]
    d_rows = weight[token, slot][:, None] * back
    per_row = jnp.concatenate([jnp.sum(rows * back, axis=-1),
                               jnp.zeros((1,), rows.dtype)])
    return d_rows, per_row[where], None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _plan(buffers, h: int, f: int, dtype, rows_a_group: float) -> int:
    """The row tile of ops/grouped_matmul.py's kernel for sorted buffers of
    `buffers` rows, a hidden size `h` and an expert width `f`, or 0 where
    one of the layer's products (forward or backward, either buffer) is
    not the kernel's to take: the compiler's `ragged_dot` runs them all."""
    tile = grouped_matmul.row_tile(rows_a_group)
    taken = all(grouped_matmul.column_tile(c, a, b, dtype, tile)
                for c in buffers for a, b in ((h, f), (f, h)))
    return tile if taken else 0


def _part(buffer, tile, x, coef, weights, order, inverse, held, load):
    """The held rows' result through a sorted buffer of `buffer` rows
    (static), all of them in it: float32[N, H]. `tile`: the kernel's row
    tile, 0 for the compiler's `ragged_dot`."""
    k = coef.shape[1]
    dtype = weights["w_gate"].dtype
    if tile:
        dot = partial(grouped_matmul.grouped, sizes=load, tm=tile)
    else:
        # the rows past the held ones are in no group here either: zeros
        dot = partial(jax.lax.ragged_dot, group_sizes=load,
                      preferred_element_type=jnp.float32)
    token, slot = order[:buffer] // k, order[:buffer] % k
    valid = held & (inverse < buffer)
    where = jnp.where(valid, inverse, buffer)
    xs = _dispatch(x, token, where, valid)
    hidden = jax.nn.silu(dot(xs, weights["w_gate"])) \
        * dot(xs, weights["w_up"])
    ys = dot(hidden.astype(dtype), weights["w_down"])
    return _combine(ys, jnp.where(valid, coef, 0.0), token, slot, where)


def _either(buffers, load, side):
    """`side(rows)` of the cut buffer where the held rows fit it, of the
    uncut one where they do not; `buffers` = (cut, uncut) rows, static."""
    cut, uncut = buffers
    if cut == uncut:
        return side(uncut)
    return jax.lax.cond(jnp.sum(load, dtype=jnp.int32) <= cut,
                        lambda: side(cut), lambda: side(uncut))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(buffers, tile, x, coef, *sort):
    """`_part` on the side of `_either` the call's load picks. Its rule
    keeps the arguments and nothing a branch made; the backward picks
    again and differentiates the picked side inside its branch (the
    forward it runs there is the layer's recomputation: the rule's own
    forward feeds nothing under `jax.checkpoint` and is dropped). `sort`:
    weights, order, inverse, held, load (no cotangent: the experts are
    frozen, the rest integers)."""
    return _either(buffers, sort[-1], lambda rows: _part(
        rows, tile, x, coef, *sort))


def _routed_fwd(buffers, tile, x, coef, *sort):
    return _routed(buffers, tile, x, coef, *sort), (x, coef, sort)


def _routed_bwd(buffers, tile, res, g):
    x, coef, sort = res

    def back(rows):
        return jax.vjp(lambda x, coef: _part(rows, tile, x, coef, *sort),
                       x, coef)[1](g)

    return (*_either(buffers, sort[-1], back), *(None,) * len(sort))


_routed.defvjp(_routed_fwd, _routed_bwd)


@partial(jax.jit, static_argnames=("first", "total"))
def held_experts(x: jax.Array, experts: jax.Array, coef: jax.Array,
                 weights: dict, first: int = 0, total: int = 0):
    """Σ over the token's assignments that land on a held expert of
    coefficient · SwiGLU_e(x): float32[N, H], and what the dispatch
    counted.

    x [N, H]; experts, coef [N, k] as `route` gives them (ids over all
    `total` experts of the model; 0: the held ones are all there are);
    weights: `w_gate`, `w_up` [E, H, F], `w_down` [E, F, H], the E experts
    `first .. first + E - 1`. The products run in the weights' dtype with
    float32 accumulation.

    counts: `load` int32[E] assignments a held expert; `dropped` int32:
    held assignments that reached no row of the sorted buffer (0 by
    construction: the uncut buffer has a row for every assignment);
    `tile_rows` int32: the rows of the (group, row tile) pairs one grouped
    product visits, `load`'s sum over it the tiles' fill; `grouped_kernel`
    int32: 1 where the product is ops/grouped_matmul.py's, 0 the
    compiler's; `buffer_rows` int32: the cut buffer's rows (static);
    `uncut` int32: 1 where the call ran on the uncut buffer, its held rows
    more than those."""
    n, k = experts.shape
    e, h, f = weights["w_gate"].shape
    dtype = weights["w_gate"].dtype
    local = experts - first
    held = (local >= 0) & (local < e)
    group = jnp.where(held, local, e).reshape(n * k)  # e: held elsewhere
    # int32 throughout, whatever jax_enable_x64 says: the sort by expert
    # (stable), its inverse, and the groups' sizes
    rank = jnp.arange(n * k, dtype=jnp.int32)
    _, order = jax.lax.sort((group, rank), num_keys=1, is_stable=True)
    _, inverse = jax.lax.sort((order, rank), num_keys=1)
    load = jnp.sum(group[:, None] == jnp.arange(e, dtype=jnp.int32)[None],
                   axis=0, dtype=jnp.int32)
    rows = jnp.sum(load, dtype=jnp.int32)
    uniform = n * k / max(total, e)  # rows a group, of a uniform router
    cut = min(n * k, -(-int(CAPACITY * uniform * e) // 8) * 8)
    tile = _plan((cut, n * k), h, f, dtype, uniform)
    out = _routed((cut, n * k), tile, x.astype(dtype), coef, weights, order,
                  inverse.reshape(n, k), held, load)
    walked = tile or grouped_matmul.COMPILER_ROW_TILE
    counts = {"load": load,
              "dropped": jnp.sum(held, dtype=jnp.int32) - rows,
              "tile_rows": walked * grouped_matmul.tile_visits(load, walked),
              "grouped_kernel": jnp.asarray(bool(tile), jnp.int32),
              "buffer_rows": jnp.asarray(cut, jnp.int32),
              "uncut": (rows > cut).astype(jnp.int32)}
    return out, counts


# {key of `dispatch_stats`: (the gauge a simulator with a registry sets to
# it after a round, its help)}: a new count is published from here
GAUGES = {
    "assignments_held": (
        "biscotti_moe_assignments_held",
        "token-expert assignments of the last round that landed on experts "
        "held here"),
    "load_max_over_mean": (
        "biscotti_moe_load_max_over_mean",
        "fullest held expert's assignments over the held experts' mean, "
        "worst sparse layer"),
    "tokens_dropped": (
        "biscotti_moe_tokens_dropped",
        "held assignments of the last round that reached no expert (must "
        "read 0)"),
    "tile_fill": (
        "biscotti_moe_tile_fill",
        "held rows of the last round's grouped products over the rows of "
        "the (group, row tile) pairs they visited"),
    "grouped_kernel": (
        "biscotti_moe_grouped_kernel",
        "1 where the round's grouped products are ops/grouped_matmul.py's "
        "kernel, 0 the compiler's ragged_dot"),
    "uncut_calls": (
        "biscotti_moe_uncut_calls",
        "calls of the last round's expert layers that ran on the uncut "
        "sorted buffer; 0 unless a block's held rows pass CAPACITY x the "
        "uniform router's"),
    "buffer_rows": (
        "biscotti_moe_buffer_rows",
        "rows of the sorted buffer a call of an expert layer runs on (the "
        "cut one: CAPACITY x the uniform router's, from the shapes)"),
    "groups_kept": (
        "biscotti_moe_groups_kept",
        "groups of experts a token's chosen experts lie in, mean over the "
        "last round's tokens and sparse layers (a group-limited router "
        "keeps at most its topk_group)"),
}


def dispatch_stats(counts: dict, blocks: float) -> dict:
    """What a round's expert dispatch counted, `{}` where it counted
    nothing: `assignments_held`, token-expert assignments that landed on
    experts held here, all sparse layers; `load_max_over_mean`, the fullest
    held expert's over the held experts' mean, worst sparse layer;
    `tokens_dropped`, held assignments that reached no expert (must read
    0); `tile_fill`, held rows over the rows of the (group, row tile) pairs
    the grouped products visited, all calls; `grouped_kernel`, 1.0 where
    those products are ops/grouped_matmul.py's; `uncut_calls`, the (block,
    sparse layer) calls that ran on the uncut sorted buffer (0 unless a
    block's held rows pass `CAPACITY` x the uniform router's);
    `buffer_rows`, the cut buffer's rows a call; and, where the router
    limits a token to some groups of experts, `groups_kept`, the groups a
    token's chosen experts lie in, mean over tokens and sparse layers.

    `counts`: `held_experts`' of every sparse layer, `load` int32[layers,
    held experts] and the rest a number a layer (and `groups_spanned`,
    `tokens` where the model's router counts them), summed over the
    `blocks` peer blocks a round walked. Reads them back to the host."""
    # not at the top: the lines of what a round traces stay where they are,
    # a Pallas call's cache key holds them (ROADMAP C10)
    import numpy as np

    if "load" not in counts:
        return {}
    load = np.asarray(counts["load"], np.float64)
    grouped = {} if "groups_spanned" not in counts else {
        "groups_kept": float(
            np.asarray(counts["groups_spanned"], np.float64).sum()
            / max(np.asarray(counts["tokens"], np.float64).sum(), 1.0))}
    return {
        **grouped,
        "assignments_held": float(load.sum()),
        "load_max_over_mean": float(np.max(load.max(axis=1)
                                           / load.mean(axis=1))),
        "tokens_dropped": float(np.asarray(counts["dropped"]).sum()),
        "tile_fill": float(load.sum() / max(
            np.asarray(counts["tile_rows"], np.float64).sum(), 1.0)),
        "grouped_kernel": float(np.asarray(
            counts["grouped_kernel"]).any()),
        "uncut_calls": float(np.asarray(counts["uncut"]).sum()),
        "buffer_rows": float(
            np.asarray(counts["buffer_rows"], np.float64).sum()
            / (load.shape[0] * blocks)),
    }
