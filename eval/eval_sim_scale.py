#!/usr/bin/env python
"""Peer-count scaling of the fully-compiled round — peers as device lanes.

The reference scales peers by booting OS processes (its published maximum
is 200 nodes across a VM fleet, eval/eval_FedSys_scale/FedSys_200_parsed;
12.4 s/iter). The TPU design maps peers onto the device instead: the
whole round — every peer's SGD step, DP noise, Krum over the contributor
set, aggregation, stake scatter — is one XLA program, and whole TRAINING
is one `lax.scan` (parallel/sim.py run_scan). This driver records
s/iteration as the peer count grows past the reference's ceiling on ONE
chip. At n >= 512 contributors the Krum stage dispatches to the fused
Pallas kernel (ops/krum_pallas, measured window [512, 4096]).

Timing: host wall-clock includes dispatch and the final sync, so on the
chip each row also records the DEVICE duration of the scan program from a
`jax.profiler` trace (utils/profiling.device_program_ms).

Artifact: eval/results/sim_scale.{json,csv}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--sizes", default="100,256,512,1024")
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--out", default="eval/results")
    args = ap.parse_args(argv)

    import jax

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.ops.krum_pallas import PALLAS_MAX_N, PALLAS_MIN_N
    from biscotti_tpu.parallel.sim import Simulator
    from biscotti_tpu.utils import jaxenv
    from biscotti_tpu.utils.profiling import device_program_ms, device_trace

    jaxenv.configure_compile_cache()
    backend = jax.default_backend()
    rows = []
    for n in [int(s) for s in args.sizes.split(",")]:
        cfg = BiscottiConfig(
            dataset=args.dataset, num_nodes=n, batch_size=10,
            epsilon=1.0, noising=True, verification=True,
            defense=Defense.KRUM, sample_percent=0.70,
            max_iterations=args.rounds, seed=0)
        sim = Simulator(cfg)
        t0 = time.perf_counter()
        sim.run_scan(args.rounds)  # compile + first run
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        w, stake, errs, accepted = sim.run_scan(args.rounds)
        wall = time.perf_counter() - t0
        device_s = None
        if backend == "tpu":
            trace_dir = tempfile.mkdtemp(prefix=f"sim_scale_{n}_")
            with device_trace(trace_dir):
                sim.run_scan(args.rounds)
            # jit_full = the whole-training scan program (sim.run_scan)
            device_s = sum(device_program_ms(trace_dir)["jit_full"]) / 1e3
        contributors = int(cfg.num_samples)
        row = {
            "nodes": n, "contributors_per_round": contributors,
            "rounds": args.rounds,
            "s_per_iter": round(wall / args.rounds, 6),
            "device_ms_per_iter": (round(device_s * 1e3 / args.rounds, 3)
                                   if device_s is not None else None),
            "wall_s": round(wall, 3), "compile_s": round(compile_s, 2),
            "final_error": round(float(errs[-1]), 4),
            "mean_accepted": round(float(accepted.mean()), 1),
            "krum_path": ("pallas"
                          if backend == "tpu"
                          and PALLAS_MIN_N <= contributors <= PALLAS_MAX_N
                          else "xla"),
        }
        rows.append(row)
        print(json.dumps(row), file=sys.stderr, flush=True)

    os.makedirs(args.out, exist_ok=True)
    payload = {
        "experiment": "sim_scale", "backend": backend,
        **jaxenv.device_info(), "dataset": args.dataset,
        "timing_note": ("s_per_iter is host wall-clock (dispatch and the "
                        "final sync included); device_ms_per_iter is the "
                        "scan program's device time from a jax.profiler "
                        "trace, taken only on the chip"),
        "reference": {"max_published_nodes": 200,
                      "fedsys_200": "12.4 s/iter (VM fleet)"},
        "rows": rows,
    }
    with open(os.path.join(args.out, "sim_scale.json"), "w") as f:
        json.dump(payload, f, indent=1)
    with open(os.path.join(args.out, "sim_scale.csv"), "w") as f:
        f.write("nodes,contributors,rounds,s_per_iter,device_ms_per_iter,"
                "final_error,krum_path\n")
        for r in rows:
            f.write(f"{r['nodes']},{r['contributors_per_round']},"
                    f"{r['rounds']},{r['s_per_iter']},"
                    f"{r['device_ms_per_iter']},{r['final_error']},"
                    f"{r['krum_path']}\n")
    print(json.dumps({"experiment": "sim_scale",
                      "max_nodes": rows[-1]["nodes"] if rows else 0,
                      "s_per_iter_at_max": rows[-1]["s_per_iter"]
                      if rows else None}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
