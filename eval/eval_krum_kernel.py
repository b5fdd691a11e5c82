#!/usr/bin/env python
"""Krum kernel benchmark — fused Pallas kernel vs the XLA matmul+top_k
path, timed from the DEVICE trace, across committee sizes.

Host-side wall-clock around a sub-millisecond kernel measures dispatch,
not the kernel, so each cell captures a `jax.profiler` trace and reads the
per-program device durations (utils/profiling.device_program_ms). Needs
the chip: a CPU trace has no device plane and the reader says so.

The reference's Krum is numpy on a verifier's CPU core behind the
go-python bridge (ML/Pytorch/client_obj.py:114-143); both columns here
are already orders of magnitude ahead of that. This artifact records
where the fused kernel overtakes the XLA lowering — top_k at k ~ n/2
lowers to a full per-row sort (`sort.1` dominates the XLA program) and
the n x n distance matrix round-trips through HBM — and validates score
agreement at every point.

Artifact: eval/results/krum_kernel.{json,csv}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 5


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--d", type=int, default=7850,
                    help="update dimension (mnist softmax default)")
    ap.add_argument("--sizes", default="512,1024,2048,4096")
    ap.add_argument("--out", default="eval/results")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from biscotti_tpu.ops.krum import krum_scores
    from biscotti_tpu.ops.krum_pallas import krum_scores_pallas
    from biscotti_tpu.utils import jaxenv
    from biscotti_tpu.utils.profiling import device_program_ms, device_trace

    jaxenv.configure_compile_cache()
    backend = jax.default_backend()
    rows = []
    for n in [int(s) for s in args.sizes.split(",")]:
        f = n // 2
        rng = np.random.default_rng(n)
        x = jnp.asarray(rng.normal(size=(n, args.d)).astype(np.float32))
        jax.block_until_ready(krum_scores(x, f))  # compile both
        jax.block_until_ready(krum_scores_pallas(x, f))

        trace_dir = tempfile.mkdtemp(prefix=f"krum_trace_{n}_")
        with device_trace(trace_dir):
            for _ in range(ITERS):
                r1 = krum_scores(x, f)
            jax.block_until_ready(r1)
            for _ in range(ITERS):
                r2 = krum_scores_pallas(x, f)
            jax.block_until_ready(r2)
        prog_ms = {name: sum(ms) / len(ms)
                   for name, ms in device_program_ms(trace_dir).items()}

        ref = np.asarray(krum_scores(x, f))
        got = np.asarray(krum_scores_pallas(x, f))
        rel = float(np.max(np.abs(ref - got) / (np.abs(ref) + 1e-6)))
        xla_ms = prog_ms.get("jit_krum_scores")
        pal_ms = prog_ms.get("jit_krum_scores_pallas")
        row = {"n": n, "d": args.d,
               "xla_device_ms": round(xla_ms, 3) if xla_ms else None,
               "pallas_device_ms": round(pal_ms, 3) if pal_ms else None,
               "speedup": (round(xla_ms / pal_ms, 2)
                           if xla_ms and pal_ms else None),
               "max_rel_err": rel, "agree": rel < 1e-4}
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    os.makedirs(args.out, exist_ok=True)
    payload = {"experiment": "krum_kernel", "backend": backend,
               **jaxenv.device_info(),
               "timing": "per-program device durations from jax.profiler "
                         "traces",
               "rows": rows}
    with open(os.path.join(args.out, "krum_kernel.json"), "w") as fp:
        json.dump(payload, fp, indent=1)
    with open(os.path.join(args.out, "krum_kernel.csv"), "w") as fp:
        fp.write("n,d,xla_device_ms,pallas_device_ms,speedup,max_rel_err\n")
        for r in rows:
            fp.write(f"{r['n']},{r['d']},{r['xla_device_ms']},"
                     f"{r['pallas_device_ms']},{r['speedup']},"
                     f"{r['max_rel_err']}\n")
    print(json.dumps({"experiment": "krum_kernel", "backend": backend,
                      "all_agree": all(r["agree"] for r in rows)}))
    return 0 if all(r["agree"] for r in rows) else 1


if __name__ == "__main__":
    raise SystemExit(main())
