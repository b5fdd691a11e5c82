#!/usr/bin/env python
"""Per-phase cost breakdown — where a protocol round's time goes.

The reference published this as a figure (ref:
usenix-eval/eval_cost_breakdown.pdf) derived from wall-clock deltas in
node logs; here every peer carries a PhaseClock and reports exact
cumulative per-phase times (sgd / crypto_commit / share_gen / verify_wait
/ miner_verify / recovery / metrics), and an optional `jax.profiler`
device trace can be captured with --trace-dir (SURVEY §5.1).

Artifacts: eval/results/cost_breakdown.json + .csv.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="mnist")
    ap.add_argument("--nodes", type=int, default=20)
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--secure-agg", type=int, default=1)
    ap.add_argument("--pipeline", type=int, default=0,
                    help="1 runs the pipelined round engine (overlapped "
                         "intake verification + speculation + batched "
                         "miner crypto)")
    ap.add_argument("--out", default="eval/results")
    ap.add_argument("--trace-dir", default="",
                    help="also capture a jax.profiler device trace here")
    ap.add_argument("--platform", default="cpu")
    args = ap.parse_args(argv)
    os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    jax.config.update("jax_platforms", args.platform)
    jax.config.update("jax_enable_x64", True)
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()

    from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts
    from biscotti_tpu.runtime.peer import PeerAgent
    from biscotti_tpu.utils.profiling import device_trace

    timeouts = Timeouts(update_s=20, block_s=60, krum_s=15, share_s=20,
                        rpc_s=20)
    cfgs = [
        BiscottiConfig(
            node_id=i, num_nodes=args.nodes, dataset=args.dataset,
            base_port=29000, secure_agg=bool(args.secure_agg), noising=True,
            verification=True, defense=Defense.KRUM,
            max_iterations=args.iterations, convergence_error=0.0,
            sample_percent=0.70, seed=2, timeouts=timeouts,
            pipeline=bool(args.pipeline), speculation=bool(args.pipeline),
            batch_intake=bool(args.pipeline),
        )
        for i in range(args.nodes)
    ]

    async def go():
        agents = [PeerAgent(c) for c in cfgs]
        results = await asyncio.gather(*(a.run() for a in agents))
        return agents, results

    import contextlib

    ctx = (device_trace(args.trace_dir) if args.trace_dir
           else contextlib.nullcontext())
    with ctx:
        agents, results = asyncio.run(go())

    # aggregate per-phase costs across peers off the TELEMETRY snapshots
    # each run() result carries (the same schema the Metrics RPC serves a
    # live scrape). obs.merge_phase_histograms is the ONE aggregation:
    # it returns per-phase count/total_s (all peers) and p50/p99 from the
    # merged log-scale histograms; the legacy totals table is a view of it
    from biscotti_tpu.tools import obs

    snaps = [r["telemetry"] for r in results]
    quantiles = obs.merge_phase_histograms(snaps)
    phases = {
        name: {"total_s": round(row["total_s"], 3),
               "calls": row["count"],
               "s_per_call": round(row["total_s"] / max(1, row["count"]), 5)}
        for name, row in quantiles.items()
    }

    # comms cost beside the compute phases: the wire table straight off
    # obs.merge_snapshots — the ONE cluster-readout definition shared
    # with the live scraper and the chaos report, bytes/round included
    wire = obs.merge_snapshots(snaps)["wire"]

    # the miner-crypto row, attributable: which slice of the miner's
    # round cost is the Pedersen/VSS commitment verification (the part
    # the batched intake amortizes), which is the Schnorr signature
    # quorum checking, and which is the Shamir share interpolation —
    # so the batched path's win shows up as a component shift in the
    # artifact, not just a smaller blob
    def _tot(*names: str) -> float:
        return round(sum(phases.get(n, {}).get("total_s", 0.0)
                         for n in names), 3)

    miner_components = {
        # one-shot batch check + incremental fold + intake digest/shape
        # validation — everything that proves shares match commitments
        "commitment_verify_s": _tot("miner_verify", "intake_fold",
                                    "intake_validate"),
        # verifier-quorum Schnorr checks at intake (batched RLC fast path)
        "signature_check_s": _tot("sig_check"),
        # Vandermonde least-squares recovery of the aggregate (memoized
        # pseudoinverse — one matmul across all chunks)
        "share_interpolation_s": _tot("recovery"),
    }

    dumps = [r["chain_dump"] for r in results]
    summary = {
        "experiment": "cost_breakdown",
        "dataset": args.dataset, "nodes": args.nodes,
        "iterations": args.iterations,
        "secure_agg": bool(args.secure_agg),
        "pipeline": bool(args.pipeline),
        "chains_equal": all(d == dumps[0] for d in dumps),
        "phases": phases,  # already ordered by -total_s (obs merge)
        "miner_crypto_components": miner_components,
        # per-phase latency quantiles from the merged telemetry histograms
        # (p50/p99 — the distribution the total_s means hide)
        "phase_quantiles": quantiles,
        # comms-bytes row next to the phase table: a round's cost is
        # compute AND bytes on the wire (the latter dominates at scale)
        "wire": wire,
        "device_trace": args.trace_dir or None,
    }
    print(json.dumps(summary))
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "cost_breakdown.json"), "w") as f:
        json.dump(summary, f, indent=1)
    with open(os.path.join(args.out, "cost_breakdown.csv"), "w") as f:
        f.write("phase,total_s,calls,s_per_call\n")
        for name, agg in summary["phases"].items():
            f.write(f"{name},{agg['total_s']},{agg['calls']},"
                    f"{agg['s_per_call']}\n")
        f.write("\nmetric,value\n")
        for comp, val in miner_components.items():
            f.write(f"miner_{comp},{val}\n")
        f.write(f"wire_out_bytes,{wire['out_bytes']}\n")
        f.write(f"wire_in_bytes,{wire['in_bytes']}\n")
        f.write(f"wire_bytes_per_round,{wire['bytes_per_round']}\n")
    return 0 if summary["chains_equal"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
