#!/usr/bin/env python
"""One state-space scan alone, at the shapes Granite-4.0-H-Micro's round
sends it: ops/ssm.py's chunked form against the token-by-token recurrence
(`ssm.sequential`), timed from the DEVICE trace (per-program durations).

A peer block of `--windows` windows of 1,024 tokens, 64 heads of 64, a
state of 128, chunks of `--chunks` (256 is the published `mamba_chunk_size`),
bfloat16 operands. Each forward alone and forward + backward with respect
to x, dt, B and C (what a `jax.checkpoint`ed layer runs in the backward
pass). Beside each time: the scan's roofline (the larger of its model FLOPs
over the bf16 peak and its least bytes over the HBM peak,
benchmark/flops/granite_hybrid.py) over the time.

Needs the chip. Artifact: <out>/ssm.json, and the table on standard error.
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
T, HEADS, WIDTH, STATE = 1024, 64, 64, 128
CHUNK = 256  # the published `mamba_chunk_size`: what the roofline counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--windows", default="1,3")
    ap.add_argument("--chunks", default="256,128,64")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark.flops import granite_hybrid as count
    from benchmark.peaks import peak
    from biscotti_tpu.ops import ssm
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
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 7)
        x = jax.random.normal(keys[0], (windows, T, HEADS, WIDTH),
                              jnp.float32).astype(dtype)
        step = jax.nn.softplus(jax.random.normal(
            keys[1], (windows, T, HEADS), jnp.float32) - 4.0)
        a = -jax.random.uniform(keys[2], (HEADS,), jnp.float32, 1.0, 16.0)
        b, c = (jax.random.normal(k, (windows, T, STATE),
                                  jnp.float32).astype(dtype) for k in keys[3:5])
        d = jnp.ones((HEADS,), jnp.float32)
        cot = jax.random.normal(keys[5], x.shape, jnp.float32)
        forms = {"sequential": lambda x, s, b, c: ssm.sequential(
            x, s, a, b, c, d)}
        for chunk in (int(v) for v in args.chunks.split(",")):
            forms[f"chunked_{chunk}"] = (
                lambda x, s, b, c, chunk=chunk: ssm.scan(x, s, a, b, c, d,
                                                         chunk))
        programs, gaps, want = {}, {}, None
        for label, form in forms.items():
            def forward(x, s, b, c, cot, form=form):
                return form(x, s, b, c)

            def both(x, s, b, c, cot, form=form):
                out, back = jax.vjp(form, x, s, b, c)
                return out, back(cot)

            for fn, passes in ((forward, "forward"), (both, "both")):
                fn.__name__ = fn.__qualname__ = f"ssm{windows}_{label}_{passes}"
                programs[label, passes] = (jax.jit(fn), fn.__name__)
            try:  # the recurrence's backward keeps a state a token
                got = jax.block_until_ready(
                    programs[label, "both"][0](x, step, b, c, cot))
                jax.block_until_ready(
                    programs[label, "forward"][0](x, step, b, c, cot))
            except Exception as e:  # and may not fit the chip
                print(f"{windows} windows, {label}: refused: "
                      f"{str(e)[-300:]}", file=sys.stderr)
                del programs[label, "forward"], programs[label, "both"]
                continue
            want = got if want is None else want  # the first that ran
            gaps[label] = [
                float(jnp.linalg.norm((g - r).astype(jnp.float32))
                      / jnp.linalg.norm(r.astype(jnp.float32)))
                for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want))]
        trace_dir = tempfile.mkdtemp(prefix="ssm_trace_")
        with device_trace(trace_dir):
            for fn, _ in programs.values():
                for _ in range(ITERS):
                    out = fn(x, step, b, c, cot)
                jax.block_until_ready(out)
        ms = device_program_ms(trace_dir)
        shape = (windows, T, HEADS, WIDTH, STATE)
        least = {
            "forward": max(
                count.scan_forward_flops(*shape, CHUNK)
                / peak(kind, "bf16_flops"),
                count.scan_forward_bytes(*shape) / peak(kind, "hbm_bytes_s")),
            "both": max(
                count.scan_step_flops(*shape, CHUNK) / peak(kind, "bf16_flops"),
                count.scan_step_bytes(*shape) / peak(kind, "hbm_bytes_s"))}
        for label in gaps:
            row = {"windows": windows, "form": label,
                   "gap_to_sequential_y_dx_ddt_db_dc": gaps[label]}
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
    with open(os.path.join(args.out, "ssm.json"), "w") as fp:
        json.dump({"experiment": "ssm", **jaxenv.device_info(),
                   "timing": "median per-program device duration, "
                             f"{ITERS} calls, jax.profiler trace",
                   "shape": {"tokens": T, "heads": HEADS, "head_dim": WIDTH,
                             "state": STATE, "dtype": "bfloat16"},
                   "rows": rows}, fp, indent=1)
    print(json.dumps({"experiment": "ssm", "rows": [
        {k: r[k] for k in ("windows", "form", "forward_ms", "both_ms")}
        for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
