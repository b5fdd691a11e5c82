#!/usr/bin/env python
"""One attention core alone, at the shapes the rounds send it: the `einsum`
form (`ops/attention.plain`) against ops/attention.py's kernel at each
admissible pair of blocks, timed from the DEVICE trace (per-program
durations).

A peer block of 3 windows of 1,024 tokens. Laguna's: 8 key/value heads of
128, the full layers' 48 query heads under the causal mask and the sliding
layers' 72 under a window of 512. DeepSeek-V2's latent attention (`--layers
mla`): 128 heads, none shared, scores that contract 192 (128 + the 64
rotary dimensions) and values of 128, causal; the 192 as they are (the
kernel's products contract a lane tile and a half) and, `padded256_*`,
zero-padded to 256 inside the timed program. Granite-4.0-H-Micro's
attention layers (`--layers granite`): 32 query heads on 8 key/value heads
of 64 | 64, causal, the scores times 1 / 64; the values as they are (half
a lane tile) and, `values128_*`, zero-padded to 128 inside the timed
program and cut again. Each forward alone and forward
+ backward (what a `jax.checkpoint`ed layer runs in the backward pass).
Beside each time: the products of the visited (query block, key block)
pairs (forward 2 bq bk (d + e), backward 2 bq bk (3 d + 2 e) more; d the
scores' width, e the values') over the chip's bfloat16 peak.

Needs the chip. Artifact: <out>/attention.json, and the table on standard
error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 8
W, T = 3, 1024
# kind: (query heads, window, key/value heads, scores' width, values', scale)
LAYERS = {"full": (48, T, 8, 128, 128, None),
          "sliding": (72, 512, 8, 128, 128, None),
          "mla": (128, T, 128, 192, 128, 192 ** -0.5 * 1.2608 ** 2),
          "granite": (32, T, 8, 64, 64, 0.015625)}
PAIRS = tuple((bq, bk) for bq in (128, 256, 512) for bk in (128, 256, 512))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=30)
    ap.add_argument("--layers", default="full,sliding",
                    help="of " + ",".join(LAYERS))
    ap.add_argument("--pairs", default="",
                    help="256x512,... (all nine where empty)")
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
    for kind in args.layers.split(","):
        heads, window, kv, d, e, scale = LAYERS[kind]
        g = heads // kv
        q = jax.random.normal(keys[0], (W, kv, g, T, d), jnp.float32)
        k = jax.random.normal(keys[1], (W, kv, T, d), jnp.float32).astype(dt)
        v = jax.random.normal(keys[2], (W, kv, T, e), jnp.float32).astype(dt)
        q = q.astype(dt)
        cot = jax.random.normal(keys[3], q.shape[:-1] + (e,), jnp.float32)
        own = at.blocks(g, T, d, dt, e)
        # every pair is timed where the chip's compiler takes it; `admitted`
        # are those whose buffers `blocks` counts inside the default VMEM
        admitted = [pair for pair in PAIRS if at._buffers(
            g, T, at._padded(d), *pair, dt.dtype.itemsize, e)
            <= at._VMEM_BUFFERS]
        forms = {"einsum": lambda q, k, v: at.plain(q, k, v, window, scale)}
        for pair in pairs_run:
            forms["kernel_%dx%d" % pair] = (
                lambda q, k, v, pair=pair: at.fused(q, k, v, window, pair,
                                                    scale))
            if d % 128:  # the scores' width in whole lane tiles, zeros added
                def padded(q, k, v, pair=pair):
                    wide = [(0, 0)] * 4 + [(0, at._padded(d) - d)]
                    return at.fused(jnp.pad(q, wide), jnp.pad(k, wide[1:]),
                                    v, window, pair,
                                    scale or d ** -0.5)

                forms["padded256_%dx%d" % pair] = padded
            if e % 128:  # the values' width in whole lane tiles
                def widened(q, k, v, pair=pair):
                    wide = [(0, 0)] * 3 + [(0, at._padded(e) - e)]
                    return at.fused(q, k, jnp.pad(v, wide), window, pair,
                                    scale)[..., :e]

                forms["values128_%dx%d" % pair] = widened
        def gaps(got, want):
            return [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                          - b.astype(jnp.float32)))
                          / jnp.max(jnp.abs(b.astype(jnp.float32))))
                    for a, b in zip(jax.tree.leaves(got),
                                    jax.tree.leaves(want))]

        programs, worst, want = {}, {}, None
        for label, form in forms.items():
            def forward(q, k, v, cot, form=form):
                return form(q, k, v)

            def both(q, k, v, cot, form=form):
                out, back = jax.vjp(form, q, k, v)
                return out, back(cot)

            made = {}
            for fn, passes in ((forward, "forward"), (both, "both")):
                fn.__name__ = fn.__qualname__ = f"{kind}_{label}_{passes}"
                made[label, passes] = (jax.jit(fn), fn.__name__)
            try:  # compiles, and the result
                got = [jax.block_until_ready(fn(q, k, v, cot))
                       for fn, _ in made.values()][-1]
            except Exception as e:  # more VMEM than a kernel may use
                print(f"{kind} {label}: refused: {str(e)[-300:]}",
                      file=sys.stderr)
                continue
            want = got if want is None else want  # the first is `einsum`
            worst[label] = gaps(got, want)
            programs.update(made)
        trace_dir = tempfile.mkdtemp(prefix="attention_trace_")
        with device_trace(trace_dir):
            for fn, _ in programs.values():
                for _ in range(ITERS):
                    out = fn(q, k, v, cot)
                jax.block_until_ready(out)
        ms = device_program_ms(trace_dir)
        for label in worst:
            pair = (tuple(int(x) for x in label.split("_")[1].split("x"))
                    if label != "einsum" else None)
            pairs = len(at.visited(T, window, *pair)) if pair else None
            row = {"layer": kind, "heads": heads, "window": window,
                   "form": label, "the_programs_own": (
                       pair == own and label.startswith("kernel")),
                   "admitted": pair in admitted,
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
               "shape": {"windows": W, "tokens": T, "dtype": "bfloat16",
                         "layers": {kind: dict(zip(
                             ("heads", "window", "kv_heads", "score_width",
                              "value_width", "scale"), LAYERS[kind]))
                             for kind in args.layers.split(",")}},
               "rows": rows}
    with open(os.path.join(args.out, "attention.json"), "w") as fp:
        json.dump(payload, fp, indent=1)
    print(json.dumps({
        "experiment": "attention",
        "einsum_over_the_programs_own": {
            r["layer"]: {p: round(e[f"{p}_ms"] / r[f"{p}_ms"], 2)
                         for p in ("forward", "both")}
            for r in rows if r["the_programs_own"]
            for e in rows if e["layer"] == r["layer"]
            and e["form"] == "einsum"}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
