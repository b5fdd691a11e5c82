#!/usr/bin/env python
"""One attention core alone, at the shapes the rounds send it: the `einsum`
form (`ops/attention.plain`) against ops/attention.py's kernel at each
admissible pair of blocks, timed from the DEVICE trace (per-program
durations).

A peer block of 3 windows of 1,024 tokens (`--windows 1,3`: also one
window, what a block walked a peer at a time sends). Laguna's: 8 key/value
heads of 128, the full layers' 48 query heads under the causal mask and the
sliding layers' 72 under a window of 512. DeepSeek-V2's latent attention
(`--layers mla`): 128 heads, none shared, scores that contract 192 (128 +
the 64 rotary dimensions, the SAME for every head) and values of 128,
causal; `shared_*`, what the model runs since PR 37: k 128 wide and the
one rotary key `[W, 1, 1,024, 64]` an operand of its own; `kernel_*`, the
192-wide key assembled outside the timed program (the kernel's products
contract a lane tile and a half) and, `padded256_*`, zero-padded to 256
inside the timed program. Granite-4.0-H-Micro's
attention layers (`--layers granite`): 32 query heads on 8 key/value heads
of 64 | 64, causal, the scores times 1 / 64; the values as they are (half
a lane tile) and, `values128_*`, zero-padded to 128 inside the timed
program and cut again. MiMo-V2.5's two kinds (`--layers mimo`: `mimo_swa`,
64 query heads on 8 key/value heads of 192 | 128 under a window of 128
with a learned sink a head, and `mimo_full`, on 4 under the causal mask),
on ONE window of 2,048 tokens (the cell's peer block is 1): a key/value
head's 8 or 16 query heads do not fit the kernel's buffers whole, so each
form is a sub-group size (`_g4`, `_g2`, `_g1`, each reading its own copy
of its key/value head, made inside the timed program) at the block
`blocks` takes for it, and at 128 x 128; `the_programs_own` marks what
`attention.group_split` picks (`_g1` at 256 x 256 since these readings).
Each forward alone and forward
+ backward (what a `jax.checkpoint`ed layer runs in the backward pass).
Beside each time: the products of the visited (query block, key block)
pairs (forward 2 bq bk (d + e), backward 2 bq bk (3 d + 2 e) more; d the
scores' width, e the values') over the chip's bfloat16 peak.

Needs the chip. Artifact: <out>/attention.json, and the table on standard
error.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 8
T = 1024  # a window's tokens (TOKENS: the kinds that have another)
# kind: (query heads, window, key/value heads, scores' width, values', scale)
LAYERS = {"full": (48, T, 8, 128, 128, None),
          "sliding": (72, 512, 8, 128, 128, None),
          "mla": (128, T, 128, 192, 128, 192 ** -0.5 * 1.2608 ** 2),
          "granite": (32, T, 8, 64, 64, 0.015625),
          "mimo_swa": (64, 128, 8, 192, 128, None),
          "mimo_full": (64, 2048, 4, 192, 128, None)}
SHARED = {"mla": 64}  # of the scores' width, a key part every head shares
TOKENS = {"mimo_swa": 2048, "mimo_full": 2048}  # a window's (others: T)
SINK = ("mimo_swa",)  # a learned float a query head in the denominator
SPLIT = {"mimo_swa": (4, 2, 1), "mimo_full": (4, 2, 1)}  # sub-group sizes
PAIRS = tuple((bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--layers", default="full,sliding",
                    help="of " + ",".join(LAYERS))
    ap.add_argument("--pairs", default="",
                    help="256x512,... (all nine where empty)")
    ap.add_argument("--windows", default="3",
                    help="windows a call, e.g. 1,3")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.peaks import peak
    from biscotti_tpu.ops import attention as at
    from biscotti_tpu.utils import jaxenv
    from biscotti_tpu.utils.profiling import device_program_ms, device_trace

    jaxenv.configure_compile_cache()
    jax.config.update("jax_enable_x64", True)  # as every entry point has it
    if jax.default_backend() != "tpu":
        print("a device time comes only from the chip", file=sys.stderr)
        return 2
    flops_s = peak(jax.devices()[0].device_kind, "bf16_flops")
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    rows = []
    pairs_run = tuple(tuple(int(x) for x in p.split("x"))
                      for p in args.pairs.split(",") if p) or PAIRS
    kinds = [kind for name in args.layers.split(",")
             for kind in (("mimo_swa", "mimo_full") if name == "mimo"
                          else (name,))]
    for kind, W in ((kind, int(w)) for kind in kinds
                    for w in (args.windows.split(",") if kind not in TOKENS
                              else "1")):
        heads, window, kv, d, e, scale = LAYERS[kind]
        g, r = heads // kv, SHARED.get(kind, 0)
        T = TOKENS.get(kind, 1024)
        sink = jax.random.normal(keys[3], (kv, g), jnp.float32) + 4.85 \
            if kind in SINK else None
        q = jax.random.normal(keys[0], (W, kv, g, T, d), jnp.float32)
        k = jax.random.normal(keys[1], (W, kv, T, d), jnp.float32).astype(dt)
        v = jax.random.normal(keys[2], (W, kv, T, e), jnp.float32).astype(dt)
        q = q.astype(dt)
        cot = jax.random.normal(keys[3], q.shape[:-1] + (e,), jnp.float32)
        own = at.blocks(g, T, d, dt, e, r)
        # the one shared part, and the whole key with it in every head
        part = k[:, :1, :, d - r:]
        if r:
            k = jnp.concatenate([k[..., :d - r], jnp.broadcast_to(
                part, k.shape[:-1] + (r,))], -1)
        # every pair is timed where the chip's compiler takes it; `admitted`
        # are those whose buffers `blocks` counts inside the default VMEM
        def admitted(pair, each):
            return at._buffers(each, T, d, *pair, dt.dtype.itemsize, e,
                               r) <= at._VMEM_BUFFERS

        # label -> (form, its operands, its (dq, dk, dv[, dshared]) as the
        # whole key's (dq, dk, dv))
        whole = (q, k, v)
        forms = {"einsum": (lambda q, k, v: at.plain(q, k, v, window, scale,
                                                     None, sink), whole)}
        for each in SPLIT.get(kind, ()):  # sub-groups of `each` heads
            taken = at.blocks(each, T, d, dt, e)
            if at.group_split(g, T, d, dt, e) == g // each:
                own = taken

            def split(q, k, v, each=each, pair=None):
                q, k, v, sinks = at.sub_groups(q, k, v, sink, g // each)
                return at.fused(q, k, v, window, pair, scale, None,
                                sinks).reshape(W, kv, g, T, e)

            for pair in dict.fromkeys(filter(None, (taken, (128, 128)))):
                forms["kernel_%dx%d_g%d" % (pair + (each,))] = (
                    functools.partial(split, pair=pair), whole)
        for pair in pairs_run if kind not in SPLIT else ():
            if r:
                forms["shared_%dx%d" % pair] = (
                    lambda q, k, v, part, pair=pair: at.fused(
                        q, k, v, window, pair, scale, part),
                    (q, k[..., :d - r], v, part))
            forms["kernel_%dx%d" % pair] = (
                lambda q, k, v, pair=pair: at.fused(q, k, v, window, pair,
                                                    scale), whole)
            if d % 128:  # the scores' width in whole lane tiles, zeros added
                def padded(q, k, v, pair=pair):
                    wide = [(0, 0)] * 4 + [(0, at._padded(d) - d)]
                    return at.fused(jnp.pad(q, wide), jnp.pad(k, wide[1:]),
                                    v, window, pair,
                                    scale or d ** -0.5)

                forms["padded256_%dx%d" % pair] = (padded, whole)
            if e % 128:  # the values' width in whole lane tiles
                def widened(q, k, v, pair=pair):
                    wide = [(0, 0)] * 3 + [(0, at._padded(e) - e)]
                    return at.fused(q, k, jnp.pad(v, wide), window, pair,
                                    scale)[..., :e]

                forms["values128_%dx%d" % pair] = (widened, whole)

        def comparable(got):
            """(out, dq, dk's own part, dv, the shared part's cotangent
            summed over the heads) of either operand list."""
            out, (dq, dk, dv, *dpart) = got
            dpart = dpart[0] if dpart else jnp.sum(
                dk[..., d - r:].astype(jnp.float32), 1, keepdims=True)
            return out, dq, dk[..., :d - r], dv, dpart

        def gaps(got, want):
            return [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32)))
                          / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(comparable(got)[:4 + bool(r)],
                                    comparable(want))]

        programs, worst, want = {}, {}, None
        for label, (form, operands) in forms.items():
            def forward(cot, *operands, form=form):
                return form(*operands)

            def both(cot, *operands, form=form):
                out, back = jax.vjp(form, *operands)
                return out, back(cot)

            made = {}
            for fn, passes in ((forward, "forward"), (both, "both")):
                fn.__name__ = fn.__qualname__ = f"{kind}{W}_{label}_{passes}"
                made[label, passes] = (jax.jit(fn), fn.__name__, operands)
            try:  # compiles, and the result
                got = [jax.block_until_ready(fn(cot, *operands))
                       for fn, _, _ in made.values()][-1]
            except Exception as e:  # more VMEM than a kernel may use
                print(f"{kind} {label}: refused: {str(e)[-300:]}",
                      file=sys.stderr)
                continue
            want = got if want is None else want  # the first is `einsum`
            worst[label] = gaps(got, want)
            programs.update(made)
        trace_dir = tempfile.mkdtemp(prefix="attention_trace_")
        with device_trace(trace_dir):
            for fn, _, operands in programs.values():
                for _ in range(ITERS):
                    out = fn(cot, *operands)
                jax.block_until_ready(out)
        ms = device_program_ms(trace_dir)
        for label in worst:
            pair = (tuple(int(x) for x in label.split("_")[1].split("x"))
                    if label != "einsum" else None)
            pairs = len(at.visited(T, window, *pair)) if pair else None
            row = {"layer": kind, "windows": W, "heads": heads,
                   "window": window,
                   "form": label, "the_programs_own": (
                       pair == own and label.startswith(
                           "shared" if r else "kernel")
                       and (kind not in SPLIT or label.endswith("_g%d" % (
                           g // at.group_split(g, T, d, dt, e))))),
                   "admitted": bool(pair) and admitted(
                       pair, int(label.rsplit("_g", 1)[1])
                       if "_g" in label else g),
                   "block_share": (round(at.block_share(T, window, *pair), 4)
                                   if pair else 1.0),
                   "gap_to_einsum_out_dq_dk_dv": worst[label]}
            for passes, products in (("forward", d + e),
                                     ("both", 4 * d + 3 * e)):
                took = sorted(ms.get(f"jit_{programs[label, passes][1]}", []))
                took = took[len(took) // 2] if took else None
                row[f"{passes}_ms"] = took and round(took, 4)
                if pair and took:
                    flop = (W * heads * pairs * products * 2 * pair[0]
                            * pair[1])
                    row[f"{passes}_share_of_bf16_peak"] = round(
                        flop / (took * 1e-3) / flops_s, 4)
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)

    os.makedirs(args.out, exist_ok=True)
    payload = {"experiment": "attention", **jaxenv.device_info(),
               "timing": "median per-program device duration, "
                         f"{ITERS} calls, jax.profiler trace",
               "shape": {"windows": args.windows, "tokens": 1024,
                         "tokens_of": TOKENS,
                         "dtype": "bfloat16",
                         "layers": {kind: dict(zip(
                             ("heads", "window", "kv_heads", "score_width",
                              "value_width", "scale"), LAYERS[kind]))
                             for kind in kinds}},
               "rows": rows}
    with open(os.path.join(args.out, "attention.json"), "w") as fp:
        json.dump(payload, fp, indent=1)
    print(json.dumps({
        "experiment": "attention",
        "einsum_over_the_programs_own": {
            "%s x %d" % (r["layer"], r["windows"]): {
                p: round(e[f"{p}_ms"] / r[f"{p}_ms"], 2)
                for p in ("forward", "both")}
            for r in rows if r["the_programs_own"]
            for e in rows if (e["layer"], e["windows"]) == (
                r["layer"], r["windows"]) and e["form"] == "einsum"}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
