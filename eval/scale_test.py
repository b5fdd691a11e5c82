#!/usr/bin/env python
"""Scale harness — N-peer clusters (up to the reference's headline N=100)
as one asyncio process over real TCP loopback, with the chain-equality
oracle and measured s/iteration artifacts.

The reference's scale evals boot 100 OS processes across an Azure fleet
(ref: eval/eval_FedSys_scale/runEval.sh, azure/azure-run/runBiscotti.sh) —
100 Python+JAX processes don't fit one box, but the peer agent is a pure
asyncio state machine, so N agents share one process and one jit cache
while still speaking real TCP RPC. Emits the reference's
`iteration,error,timestamp` CSV shape (ref: eval_performance/parseLogs.py)
plus a JSON summary with s/iter, directly comparable to
BASELINE.md (Biscotti 38.2-42.0 s/iter, FedSys 7.1-9.1 s/iter @ 100 nodes).

Usage:
    python eval/scale_test.py --nodes 100 --dataset creditcard \
        [--fedsys] [--secure-agg 1] [--noising 1] [--verification 1] \
        [--iterations 3] [--out eval/results]
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

try:  # large-N clusters need sockets: lift the soft fd limit to the hard cap
    import resource

    _soft, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if _soft < _hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (_hard, _hard))
except Exception:
    pass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_cfgs(args):
    from biscotti_tpu.config import BiscottiConfig, Defense, Timeouts

    timeouts = Timeouts().scaled(
        args.nodes, args.num_verifiers, args.num_miners,
        defense_is_krum=args.defense == "KRUM")
    extra = {}
    if args.share_redundancy == "auto":
        # single source of truth: probe the EXACT config this run builds;
        # fall back to reference parity (r=2.0) only if its total_shares
        # guarantee check rejects the hardened default
        try:
            _probe = BiscottiConfig(
                node_id=0, num_nodes=args.nodes, dataset=args.dataset,
                num_miners=args.num_miners,
                num_verifiers=args.num_verifiers,
                num_noisers=args.num_noisers)
            _probe.total_shares
        except ValueError:
            print("[scale] share_redundancy=auto: hardened default "
                  "unavailable for this committee shape, using r=2.0",
                  file=sys.stderr)
            extra["share_redundancy"] = 2.0
    elif args.share_redundancy is not None:
        extra["share_redundancy"] = float(args.share_redundancy)
    cfgs = []
    for i in range(args.nodes):
        cfgs.append(BiscottiConfig(
            node_id=i, num_nodes=args.nodes, dataset=args.dataset,
            model_name=args.model_name, base_port=args.base_port,
            num_miners=args.num_miners, num_verifiers=args.num_verifiers,
            num_noisers=args.num_noisers,
            secure_agg=bool(args.secure_agg), noising=bool(args.noising),
            verification=bool(args.verification),
            fedsys=args.fedsys, defense=Defense(args.defense),
            epsilon=args.epsilon, poison_fraction=args.poison,
            max_iterations=args.iterations, convergence_error=0.0,
            sample_percent=args.sample_percent, seed=args.seed,
            timeouts=timeouts, **extra,
        ))
    return cfgs


async def run_cluster(cfgs, log_dir="", key_dir="", geo_regions=0,
                      geo_rtt_s=0.0, pool_conns=0, use_stepper=True):
    from biscotti_tpu.runtime.peer import PeerAgent
    from biscotti_tpu.runtime.rpc import geo_latency

    if use_stepper:
        # all agents share one HiveStepper: every peer's SGD runs as ONE
        # batched XLA dispatch per round, and the per-round convergence
        # metric is computed once instead of N times (VERDICT r3 lever —
        # runtime/hive.py; multi-process deployments keep per-agent
        # dispatch, this sharing needs co-located peers). Real TCP between
        # the agents and every announce, as without the stepper
        from biscotti_tpu.runtime.hive import Hive

        agents = Hive(cfgs[0], key_dir=key_dir, log_dir=log_dir,
                      loopback=False, skip_local_announce=False).agents
    else:
        agents = [
            PeerAgent(c, key_dir=key_dir,
                      log_path=os.path.join(log_dir,
                                            f"events_{c.node_id}.jsonl")
                      if log_dir else "")
            for c in cfgs
        ]
    if pool_conns:
        # single-box fd budget: every loopback conn costs 2 fds in-process
        # (~ 2*N*cap total), so very large N needs a smaller per-peer pool
        for a in agents:
            a.pool.max_conns = pool_conns
    if geo_regions > 1:
        n = len(cfgs)
        for a in agents:
            a.pool.latency = geo_latency(a.id, a.cfg.base_port,
                                         geo_regions, n, geo_rtt_s)
    stagger_s = 0.025

    async def launch(i, a):
        # stagger like the reference's shell launch loop (runBiscotti.sh
        # starts processes one ssh at a time): N simultaneous announces
        # hold O(N²) busy sockets cluster-wide before pool eviction can
        # close any — single-box that transiently blew the 20k fd limit
        # at N≳150
        await asyncio.sleep(i * stagger_s)
        return await a.run()

    t0 = time.time()
    results = await asyncio.gather(*(launch(i, a)
                                     for i, a in enumerate(agents)))
    # wall charges the protocol, not the harness: subtract the launch
    # ramp (last agent starts (N-1)*stagger late; s_per_iter is computed
    # from round-log timestamps and is unaffected either way). Both the
    # raw and ramp-adjusted walls are surfaced in the artifact because
    # early-launched agents do real protocol work during the ramp, so the
    # adjusted number slightly flatters the wall/n_blocks fallback path
    # (ADVICE r3).
    raw_wall = time.time() - t0
    wall = raw_wall - (len(agents) - 1) * stagger_s
    return agents, results, wall, raw_wall


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--dataset", default="creditcard")
    ap.add_argument("--model", dest="model_name", default="",
                    help="override the dataset's default model (zoo name, "
                         "e.g. cifar_cnn / mnist_cnn / svm)")
    ap.add_argument("--base-port", type=int, default=26000)
    ap.add_argument("--iterations", type=int, default=3)
    ap.add_argument("--fedsys", action="store_true")
    ap.add_argument("--secure-agg", type=int, default=0)
    ap.add_argument("--noising", type=int, default=0)
    ap.add_argument("--verification", type=int, default=0)
    ap.add_argument("--defense", default="KRUM")
    ap.add_argument("--epsilon", type=float, default=1.0)
    ap.add_argument("--poison", type=float, default=0.0)
    ap.add_argument("--sample-percent", type=float, default=0.70)
    ap.add_argument("--num-miners", type=int, default=3)
    ap.add_argument("--num-verifiers", type=int, default=3)
    ap.add_argument("--num-noisers", type=int, default=2)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--stepper", type=int, default=1,
                    help="share one HiveStepper across the in-process "
                         "agents (batched SGD dispatch + one convergence "
                         "eval per round); 0 = per-agent dispatch, the "
                         "multi-process deployment shape")
    ap.add_argument("--pool-conns", type=int, default=0,
                    help="override each peer's connection-pool cap "
                         "(0 = library default); N>=300 single-box needs "
                         "a smaller pool to fit the 20k fd budget")
    ap.add_argument("--share-redundancy", default=None,
                    help="a float overrides the config default (1.5 "
                         "hardened); 'auto' keeps the default where its "
                         "anti-differencing guarantee holds and falls "
                         "back to the reference's r=2.0 for committee "
                         "shapes where it is structurally unavailable "
                         "(config.py total_shares)")
    ap.add_argument("--out", default="")
    ap.add_argument("--tag", default="")
    ap.add_argument("--log-dir", default="")
    ap.add_argument("--geo-regions", type=int, default=0,
                    help="split peers into this many synthetic regions; "
                         "cross-region RPCs pay --geo-rtt-ms (0 = off)")
    ap.add_argument("--geo-rtt-ms", type=float, default=80.0,
                    help="cross-region round-trip time in milliseconds")
    ap.add_argument("--key-dir", default="",
                    help="dealer key directory (tools/keygen.py); 'auto' "
                         "generates one for this run's dims/nodes so the "
                         "cluster pays the FULL crypto plane — Pedersen "
                         "commitment MSMs in plain mode (the reference's "
                         "O(d) bn256 cost, kyber.go:533-562), dealer "
                         "Schnorr identities, VRF noise keys")
    ap.add_argument("--platform", default="cpu",
                    help="jax platform for the in-process cluster: one "
                         "agent per peer makes N small dispatches per "
                         "round, which is host work; the batched device "
                         "plane is the hive's (runtime/hive.py)")
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = args.platform
    import jax

    jax.config.update("jax_platforms", args.platform)
    jax.config.update("jax_enable_x64", True)
    # persistent XLA compilation cache: the krum kernel at CNN dims costs
    # ~30 s to compile, which a 3-5 iteration artifact run would otherwise
    # charge to the first round's wall clock every single run
    from biscotti_tpu.utils import jaxenv

    jaxenv.configure_compile_cache()

    cfgs = build_cfgs(args)
    key_dir = args.key_dir
    if key_dir == "auto":
        from biscotti_tpu.tools import keygen

        key_dir = keygen.make_ephemeral_dir(args.dataset, args.nodes,
                                            args.model_name)
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    agents, results, wall, raw_wall = asyncio.run(
        run_cluster(cfgs, args.log_dir, key_dir,
                    geo_regions=args.geo_regions,
                    geo_rtt_s=args.geo_rtt_ms / 1000.0,
                    pool_conns=args.pool_conns,
                    use_stepper=bool(args.stepper)))

    dumps = [r["chain_dump"] for r in results]
    equal = all(d == dumps[0] for d in dumps)
    n_blocks = len(dumps[0].splitlines()) - 1  # minus genesis
    nonempty = sum(1 for line in dumps[0].splitlines()[1:]
                   if "ndeltas=0" not in line)

    # s/iter from node 0's round log timestamps (the reference's method:
    # wall-clock deltas between per-iteration log lines)
    rows = [tuple(x.split(",")) for x in results[0]["logs"]]
    if len(rows) >= 2:
        ts = [float(r[2]) for r in rows]
        s_per_iter = (ts[-1] - ts[0]) / (len(ts) - 1)
    else:
        s_per_iter = wall / max(1, n_blocks)

    from biscotti_tpu.data.datasets import spec as dspec

    mode = "fedsys" if args.fedsys else "biscotti"
    attack = {}
    if args.poison > 0:
        # live-protocol attack accounting: score the CHAIN's final model
        # (the one every peer converged on — chains_equal asserts it) on
        # the attack-source split, with both the reference's 1−accuracy
        # metric and the stricter predicted-as-target rate
        # (trainer.attack_rate / attack_success_rate)
        w_final = agents[0].chain.latest_gradient()
        tr = agents[0].trainer
        attack = {
            "poison_fraction": args.poison,
            "attack_rate": round(tr.attack_rate(w_final), 4),
            "attack_success_rate": round(
                tr.attack_success_rate(w_final), 4),
        }
        # stake-decay evidence: the PoS anti-capture mechanism is
        # "rejected poisoners lose election weight" — record the final
        # per-group mean stake so the claim is measured, not inferred
        from biscotti_tpu.parallel.sim import _poisoned_ids

        stake_map = agents[0].chain.latest_stake_map()
        poisoned = _poisoned_ids(args.nodes, args.poison)
        p_stakes = [stake_map.get(i, 0) for i in poisoned]
        h_stakes = [stake_map.get(i, 0) for i in range(args.nodes)
                    if i not in poisoned]
        if p_stakes and h_stakes:
            attack["mean_stake_poisoned"] = round(
                sum(p_stakes) / len(p_stakes), 1)
            attack["mean_stake_honest"] = round(
                sum(h_stakes) / len(h_stakes), 1)
    summary = {
        "mode": mode, "nodes": args.nodes, "dataset": args.dataset,
        "model": args.model_name or "default",
        # TRIMMED_MEAN acts at MINER aggregation (peer.py), independent of
        # the verification flag; mask defenses need verifiers to run
        "defense": (args.defense
                    if args.verification or args.defense == "TRIMMED_MEAN"
                    else "NONE"),
        "num_verifiers": args.num_verifiers, "num_miners": args.num_miners,
        "num_noisers": args.num_noisers,
        # all N peers share this host: s/iter here charges every peer's
        # compute+crypto to os.cpu_count() cores, where the reference's
        # fleet numbers (BASELINE.md) spread 100 nodes over ~20 multi-core
        # VMs — normalize before comparing
        "host_cores": os.cpu_count(),
        "secure_agg": bool(args.secure_agg), "noising": bool(args.noising),
        "verification": bool(args.verification),
        # keyed=True ⇒ the dealer key plane is live: plain-mode commitments
        # are Pedersen MSMs (the reference's O(d) cost, kyber.go:533-562),
        # not the keyless SHA-256 stand-in
        "keyed": bool(key_dir),
        "batched_stepper": bool(args.stepper),
        "geo_regions": args.geo_regions,
        "geo_rtt_ms": args.geo_rtt_ms if args.geo_regions > 1 else 0,
        **attack,
        "iterations_run": n_blocks, "nonempty_blocks": nonempty,
        "chains_equal": equal, "wall_s": round(wall, 2),
        "raw_wall_s": round(raw_wall, 2),
        "launch_ramp_s": round(raw_wall - wall, 2),
        "s_per_iter": round(s_per_iter, 3),
        "final_error": results[0]["final_error"],
        "data_note": (
            "REAL data (bundled corpus, see data/datasets.py; shards may "
            "reuse rows when nodes exceed the corpus shard capacity)"
            if dspec(args.dataset).real else
            "synthetic Gaussian shards (zero-egress env); "
            "errors not comparable to real-data curves"),
        # per-phase wall-clock accounting (PhaseClock): node 0 plus the
        # node with the largest total, for diagnosing where round time goes
        "phases_node0": results[0].get("phases", {}),
        "phases_max": max(
            (r.get("phases", {}) for r in results),
            key=lambda p: sum(v.get("total_s", 0) for v in p.values())),
    }
    print(json.dumps(summary))
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        tag = args.tag or f"{mode}_{args.dataset}_{args.nodes}"
        with open(os.path.join(args.out, f"scale_{tag}.json"), "w") as f:
            json.dump(summary, f, indent=1)
        with open(os.path.join(args.out, f"scale_{tag}.csv"), "w") as f:
            for r in results[0]["logs"]:
                f.write(r + "\n")
    if not equal:
        print("[scale] FAIL: chain-equality oracle violated", file=sys.stderr)
        return 1
    if nonempty == 0:
        print("[scale] FAIL: no non-empty blocks minted", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
