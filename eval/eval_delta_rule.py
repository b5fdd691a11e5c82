#!/usr/bin/env python
"""One gated delta rule alone, at the shapes Qwen3-Next-80B-A3B's round
sends it: ops/delta_rule.py's fused kernel (`kernel_<chunk>`: what
`delta_rule.rule` runs at these shapes since PR 39) beside its `jax.numpy`
chunked form (`chunked_<chunk>`: the kernel's oracle, and what the round
ran before) and the token-by-token recurrence (`delta_rule.sequential`),
timed from the DEVICE trace (per-program durations).

A peer block of `--windows` windows of 1,024 tokens, 16 key heads of 128
serving 32 value heads of 128, chunks of `--chunks`, bfloat16 operands.
Each forward alone and forward + backward with respect to q, k, v, g and
beta (what a `jax.checkpoint`ed layer runs in the backward pass). Beside
each time: the rule's roofline (the larger of its model FLOPs over the
bf16 peak and its least bytes over the HBM peak,
benchmark/flops/qwen3_next.py) over the time.

Needs the chip. Artifact: <out>/delta_rule.json, and the table on standard
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
T, KEY_HEADS, VALUE_HEADS, WIDTH = 1024, 16, 32, 128


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--windows", default="1,3")
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--forms", default="sequential,chunked,kernel",
                    help="which of the three forms to time")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.flops import qwen3_next as count
    from benchmark.peaks import peak
    from biscotti_tpu.ops import delta_rule
    from biscotti_tpu.utils import jaxenv
    from biscotti_tpu.utils.profiling import device_program_ms, device_trace

    jaxenv.configure_compile_cache()
    jax.config.update("jax_enable_x64", True)  # as every entry point has it
    if jax.default_backend() != "tpu":
        print("a device time comes only from the chip", file=sys.stderr)
        return 2
    kind = jax.devices()[0].device_kind
    dtype = jnp.bfloat16
    rows = []
    for windows in (int(w) for w in args.windows.split(",")):
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        q, k = (delta_rule.l2norm(jax.random.normal(
            key, (windows, T, KEY_HEADS, WIDTH), jnp.float32))
            for key in keys[:2])
        q = (q * WIDTH ** -0.5).astype(dtype)
        k = k.astype(dtype)
        v = jax.random.normal(keys[2], (windows, T, VALUE_HEADS, WIDTH),
                              jnp.float32).astype(dtype)
        g = -jax.nn.softplus(jax.random.normal(
            keys[3], (windows, T, VALUE_HEADS), jnp.float32) - 3.0)
        beta = jax.nn.sigmoid(jax.random.normal(
            keys[4], (windows, T, VALUE_HEADS), jnp.float32))
        cot = jax.random.normal(keys[5], v.shape, jnp.float32)
        asked = args.forms.split(",")
        forms = {"sequential": delta_rule.sequential} \
            if "sequential" in asked else {}
        for chunk in (int(c) for c in args.chunks.split(",")):
            if "chunked" in asked:
                forms[f"chunked_{chunk}"] = (
                    lambda *a, chunk=chunk: delta_rule.chunked(*a, chunk))
            if "kernel" in asked and delta_rule.fits(T, WIDTH, WIDTH, chunk,
                                                     dtype):
                forms[f"kernel_{chunk}"] = (
                    lambda *a, chunk=chunk: delta_rule.fused(*a, chunk))
        programs, gaps, want = {}, {}, None
        for label, form in forms.items():
            def forward(q, k, v, g, beta, cot, form=form):
                return form(q, k, v, g, beta)

            def both(q, k, v, g, beta, cot, form=form):
                out, back = jax.vjp(form, q, k, v, g, beta)
                return out, back(cot)

            for fn, passes in ((forward, "forward"), (both, "both")):
                fn.__name__ = fn.__qualname__ = \
                    f"rule{windows}_{label}_{passes}"
                programs[label, passes] = (jax.jit(fn), fn.__name__)
            try:  # the recurrence's backward keeps a state a token
                got = jax.block_until_ready(
                    programs[label, "both"][0](q, k, v, g, beta, cot))
                jax.block_until_ready(
                    programs[label, "forward"][0](q, k, v, g, beta, cot))
            except Exception as e:  # and may not fit the chip
                print(f"{windows} windows, {label}: refused: "
                      f"{str(e)[-300:]}", file=sys.stderr)
                del programs[label, "forward"], programs[label, "both"]
                continue
            want = got if want is None else want  # the first that ran
            gaps[label] = [
                float(jnp.linalg.norm((a - r).astype(jnp.float32))
                      / jnp.linalg.norm(r.astype(jnp.float32)))
                for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        trace_dir = tempfile.mkdtemp(prefix="rule_trace_")
        with device_trace(trace_dir):
            for fn, _ in programs.values():
                for _ in range(ITERS):
                    out = fn(q, k, v, g, beta, cot)
                jax.block_until_ready(out)
        ms = device_program_ms(trace_dir)
        shape = (windows, T, KEY_HEADS, WIDTH, VALUE_HEADS, WIDTH)
        least = {
            "forward": max(
                count.rule_forward_flops(*shape) / peak(kind, "bf16_flops"),
                count.rule_forward_bytes(*shape) / peak(kind, "hbm_bytes_s")),
            "both": max(
                count.rule_step_flops(*shape) / peak(kind, "bf16_flops"),
                count.rule_step_bytes(*shape) / peak(kind, "hbm_bytes_s"))}
        for label in gaps:
            row = {"windows": windows, "form": label,
                   "gap_to_first_o_dq_dk_dv_dg_dbeta": gaps[label]}
            for passes in ("forward", "both"):
                took = sorted(ms.get(f"jit_{programs[label, passes][1]}", []))
                took = took[len(took) // 2] if took else None
                row[f"{passes}_ms"] = took and round(took, 4)
                if took:
                    row[f"{passes}_roofline_share"] = round(
                        least[passes] / (took * 1e-3), 4)
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "delta_rule.json"), "w") as fp:
        json.dump({"experiment": "delta_rule", **jaxenv.device_info(),
                   "timing": "median per-program device duration, "
                             f"{ITERS} calls, jax.profiler trace",
                   "shape": {"tokens": T, "key_heads": KEY_HEADS,
                             "value_heads": VALUE_HEADS, "head_dim": WIDTH,
                             "dtype": "bfloat16"},
                   "rows": rows}, fp, indent=1)
    print(json.dumps({"experiment": "delta_rule", "rows": [
        {k: r[k] for k in ("windows", "form", "forward_ms", "both_ms")}
        for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
