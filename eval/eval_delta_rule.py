#!/usr/bin/env python
"""One gated delta rule alone, at the shapes a model's round sends it
(`--shapes`): ops/delta_rule.py's fused kernel (`kernel_<chunk>`: what
`delta_rule.rule` runs at these shapes) beside its `jax.numpy` chunked
form (`chunked_<chunk>`: the kernel's oracle, and what the round ran
before) and the token-by-token recurrence (`delta_rule.sequential`),
timed from the DEVICE trace (per-program durations).

A peer block of `--windows` windows of 1,024 tokens, chunks of `--chunks`,
bfloat16 operands: `qwen3_next`, 16 key heads of 128 serving 32 value
heads of 128, beta in (0, 1); `olmo_hybrid`, 30 key heads of 96 serving 30
value heads of 192, beta in (0, 2), which the kernel takes laid in 128 |
256 with zero columns (`delta_rule.laid`). `--heads-a-step` times the
kernel at other steps of its grid than `delta_rule.heads_a_step` takes
(`kernel_<chunk>_step<key heads>`: the table PR 48 chose its layout
from). Each forward alone and forward + backward with respect to q, k, v,
g and beta (what a `jax.checkpoint`ed layer runs in the backward pass).
Beside each time: the rule's roofline at the MODEL's widths (the larger
of its model FLOPs over the bf16 peak and its least bytes over the HBM
peak, benchmark/flops/qwen3_next.py) over the time.

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
T = 1024
# (key heads, key width, value heads, value width, beta's range)
SHAPES = {"qwen3_next": (16, 128, 32, 128, 1.0),
          "olmo_hybrid": (30, 96, 30, 192, 2.0)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=38)
    ap.add_argument("--windows", default="1,3")
    ap.add_argument("--chunks", default="64")
    ap.add_argument("--forms", default="sequential,chunked,kernel",
                    help="which of the three forms to time")
    ap.add_argument("--shapes", default="qwen3_next", choices=sorted(SHAPES))
    ap.add_argument("--heads-a-step", default="",
                    help="key heads a step to time the kernel at besides "
                         "its own choice, e.g. 2,3,6")
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
    key_heads, key_width, value_heads, value_width, top = SHAPES[args.shapes]
    chosen = delta_rule.heads_a_step
    rows = []
    for windows in (int(w) for w in args.windows.split(",")):
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        q, k = (delta_rule.l2norm(jax.random.normal(
            key, (windows, T, key_heads, key_width), jnp.float32))
            for key in keys[:2])
        q = (q * key_width ** -0.5).astype(dtype)
        k = k.astype(dtype)
        v = jax.random.normal(keys[2], (windows, T, value_heads, value_width),
                              jnp.float32).astype(dtype)
        g = -jax.nn.softplus(jax.random.normal(
            keys[3], (windows, T, value_heads), jnp.float32) - 3.0)
        beta = top * jax.nn.sigmoid(jax.random.normal(
            keys[4], (windows, T, value_heads), jnp.float32))
        cot = jax.random.normal(keys[5], v.shape, jnp.float32)
        asked = args.forms.split(",")
        forms = {"sequential": delta_rule.sequential} \
            if "sequential" in asked else {}
        for chunk in (int(c) for c in args.chunks.split(",")):
            if "chunked" in asked:
                forms[f"chunked_{chunk}"] = (
                    lambda *a, chunk=chunk: delta_rule.chunked(*a, chunk))
            if "kernel" in asked and delta_rule.plan(
                    key_heads, T, key_width, value_width, chunk, dtype,
                    heads=value_heads)["kernel"]:
                forms[f"kernel_{chunk}"] = (
                    lambda *a, chunk=chunk: delta_rule.rule(*a, chunk))
                for held in filter(None, args.heads_a_step.split(",")):
                    forms[f"kernel_{chunk}_step{held}"] = forms[
                        f"kernel_{chunk}"]
        programs, gaps, want = {}, {}, None
        for label, form in forms.items():
            # the kernel's callers are jitted on shapes only: another step
            # of its grid is another trace of them
            held = label.partition("_step")[2]
            delta_rule.heads_a_step = (
                lambda groups, heads, held=int(held): held) if held \
                else chosen
            jax.clear_caches()

            def forward(q, k, v, g, beta, cot, form=form):
                return form(q, k, v, g, beta)

            def both(q, k, v, g, beta, cot, form=form):
                out, back = jax.vjp(form, q, k, v, g, beta)
                return out, back(cot)

            try:  # the recurrence's backward keeps a state a token
                for fn, passes in ((forward, "forward"), (both, "both")):
                    fn.__name__ = fn.__qualname__ = \
                        f"rule{windows}_{label}_{passes}"
                    # compiled here and now, under this label's step: the
                    # caches are cleared before the next
                    programs[label, passes] = (
                        jax.jit(fn).lower(q, k, v, g, beta, cot).compile(),
                        fn.__name__)
                got = jax.block_until_ready(
                    programs[label, "both"][0](q, k, v, g, beta, cot))
                jax.block_until_ready(
                    programs[label, "forward"][0](q, k, v, g, beta, cot))
            except Exception as e:  # and may not fit the chip
                print(f"{windows} windows, {label}: refused: "
                      f"{str(e)[-300:]}", file=sys.stderr)
                programs.pop((label, "forward"), None)
                programs.pop((label, "both"), None)
                continue
            want = got if want is None else want  # the first that ran
            gaps[label] = [
                float(jnp.linalg.norm((a - r).astype(jnp.float32))
                      / jnp.linalg.norm(r.astype(jnp.float32)))
                for a, r in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        delta_rule.heads_a_step = chosen
        trace_dir = tempfile.mkdtemp(prefix="rule_trace_")
        with device_trace(trace_dir):
            for fn, _ in programs.values():
                for _ in range(ITERS):
                    out = fn(q, k, v, g, beta, cot)
                jax.block_until_ready(out)
        ms = device_program_ms(trace_dir)
        shape = (windows, T, key_heads, key_width, value_heads, value_width)
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
                   "shape": {"tokens": T, "key_heads": key_heads,
                             "key_dim": key_width,
                             "value_heads": value_heads,
                             "value_dim": value_width, "beta_below": top,
                             "dtype": "bfloat16"},
                   "rows": rows}, fp, indent=1)
    print(json.dumps({"experiment": "delta_rule", "rows": [
        {k: r[k] for k in ("windows", "form", "forward_ms", "both_ms")}
        for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
