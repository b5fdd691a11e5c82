#!/usr/bin/env python
"""Headline benchmark — crypto-inclusive wall-clock per training iteration
across the five BASELINE.json configs.

One Biscotti iteration's critical path (deployment model: one peer per
TPU host, as the reference runs one peer per process across VMs) is:

    device round   all peers' SGD + DP noise + Krum + aggregation as ONE
                   vmapped XLA program on the chip (parallel/sim.py)
  + worker crypto  ONE peer's quantize → Pedersen-VSS chunk commitments →
                   blinding rows → int64 Shamir shares (host C++/CPU;
                   peers run this in parallel in deployment, so one
                   peer's cost is the critical-path term)
  + miner crypto   the busiest miner's intake under the PIPELINED engine:
                   share slices fold into the round's VSS accumulator as
                   they arrive (miner_fold_s, overlapped with the intake
                   network window) and mint time pays only the RLC settle
                   (miner_crypto_s). The pre-pipeline whole-intake lump is
                   kept as miner_crypto_oneshot_s for the r02–r05
                   trajectory (× NUM_SAMPLES/2, the mint trigger,
                   ref: main.go:345-363)
  + recovery       leader's Vandermonde least-squares recovery of the
                   aggregate (CPU-pinned int64/f64 path, see
                   ops/secretshare.py docstring: TPUs have no exact s64
                   matmul — a deliberate, validated host fallback)

Round 1's bench measured only the device round and reported 32,965× —
real, but it omitted exactly the costs that dominated the reference's
38.2 s/iter (the O(d) EC work per update, SURVEY §7.3). This bench times
every component and also validates the int64 share pipeline end-to-end
(shares → aggregate → recover == Σ quantized) on this host.

Disclosure: datasets are synthetic Gaussian shards (zero-egress build
environment) with reference dimensions — error columns are NOT comparable
to the reference's real-MNIST curves; timing is, since shapes match.
vs_baseline compares against the reference's published fleet numbers
(BASELINE.md: 38.2 s/iter, 100 nodes over ~20 multi-VM CPU cores);
configs the reference never published numbers for carry vs_baseline null.

Prints ONE compact JSON line on stdout: {"metric", "value", "unit",
"vs_baseline"}. Per-config detail rows go to eval/results/bench_detail.json
and stderr.
"""

import json
import os
import sys
import time
import traceback

BASELINE_MNIST_S_PER_ITER = 38.2  # BASELINE.md row 1, low end

# Peak bf16 FLOP/s of one chip, keyed by the `device_kind` JAX reports.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16). The
# MFU column divides by this number, so it is the BF16-peak utilization;
# the sim computes in f32, whose MXU peak is lower, making the printed MFU
# conservative either way. Biscotti's models are 8k-164k params —
# thousands of times below the size where one chip saturates — so the
# device round is dispatch/latency-bound and MFU is honestly tiny; the
# number exists to say so with data. A device that is not in the table is
# an error, not a default: a utilization against another chip's peak is
# no measurement.
PEAK_FLOPS_BF16 = {"TPU v5 lite": 1.97e14}


def peak_flops(device_kind):
    try:
        return PEAK_FLOPS_BF16[device_kind]
    except KeyError:
        raise SystemExit(
            f"bench: no peak FLOP/s on record for device_kind "
            f"{device_kind!r} — the bench measures the chip; add the kind "
            f"to PEAK_FLOPS_BF16 with its source") from None


def _timeit(fn, warm=1, iters=3):
    for _ in range(warm):
        fn()
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters


def _progress(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _round_frame_bytes(cfg, w64, accepted, codec="raw64"):
    """Per-frame byte sizes (verify, submit, block) for one round,
    measured by encoding the ACTUAL frames (runtime/wire.py packers +
    messages.py codec path) with `w64` as the representative
    delta/model vector — the shared kernel of wire_round_bytes and
    cross_host_round_bytes."""
    import numpy as np

    from biscotti_tpu.ledger.block import Block, BlockData, Update
    from biscotti_tpu.ops import secretshare as ss
    from biscotti_tpu.runtime import codecs as wcodecs
    from biscotti_tpu.runtime import messages as msgs
    from biscotti_tpu.runtime import wire as rwire

    wc = wcodecs.get(codec)
    kw = dict(codec=None if wc.name == wcodecs.RAW else wc.name)
    d = len(w64)
    delta, _ = wc.transform(np.asarray(w64, np.float64),
                            topk_k=max(1, int(round(cfg.wire_topk * d))))
    gw = wc.transform_dense(np.asarray(w64, np.float64))
    it = 1

    # worker -> verifier: redacted update, noised copy only (f32 on the
    # wire since PR before this one; the codec can still zlib it)
    redacted = Update(source_id=1, iteration=it,
                      delta=np.zeros(0, np.float64), commitment=b"\0" * 32,
                      noised_delta=np.asarray(delta, np.float32))
    vmeta, varrays = rwire.pack_update(redacted)
    verify = len(msgs.encode("VerifyUpdateKRUM", vmeta, varrays, **kw))

    if cfg.secure_agg:
        c = ss.num_chunks(d, cfg.poly_size)
        submit = len(msgs.encode("RegisterSecret", {
            "iteration": it, "source_id": 1, "miner_index": 0,
            "commitment": "00" * 32,
        }, {
            "share_rows": np.ones((cfg.shares_per_miner, c), np.int64),
            "blind_rows": np.ones((cfg.shares_per_miner, c, 32), np.uint8),
            "comms": np.ones((c, cfg.poly_size, 64), np.uint8),
        }, **kw))
        blk_updates = [Update(source_id=1, iteration=it,
                              delta=np.zeros(0, np.float64),
                              commitment=b"\0" * 32, accepted=True)]
    else:
        u = Update(source_id=1, iteration=it, delta=delta,
                   commitment=b"\0" * 32)
        umeta, uarrays = rwire.pack_update(u)
        submit = len(msgs.encode("RegisterUpdate", umeta, uarrays, **kw))
        blk_updates = [Update(source_id=1, iteration=it, delta=delta,
                              commitment=b"\0" * 32, accepted=True)]

    blk = Block(data=BlockData(iteration=it, global_w=gw,
                               deltas=blk_updates * max(1, accepted)),
                prev_hash=b"\0" * 32,
                stake_map={i: 10 for i in range(cfg.num_nodes)}).seal()
    bmeta, barrays = rwire.pack_block(blk)
    block = len(msgs.encode("RegisterBlock", bmeta, barrays, **kw))
    return verify, submit, block


def wire_round_bytes(cfg, w64, accepted, codec="raw64"):
    """Cluster-wide protocol bytes for ONE round:

        num_samples × (num_verifiers × verify + num_miners × submit)
      + (num_nodes − 1) × block broadcast

    Lossy codecs are applied the way the live runtime applies them —
    transform BEFORE packing (lossy-before-commit), so the frame sizes
    here are exactly what the wire plane produces. Crypto tensors
    (shares, blinds, VSS commitments) are sized from the config and
    always travel lossless, which is why secure-agg rows compress less
    than their plain-mode cousins: the crypto dominates and is
    incompressible by design."""
    verify, submit, block = _round_frame_bytes(cfg, w64, accepted,
                                               codec=codec)
    n_s = cfg.num_samples
    return int(n_s * (cfg.num_verifiers * verify + cfg.num_miners * submit)
               + (cfg.num_nodes - 1) * block)


def cross_host_round_bytes(cfg, w64, accepted, codec="raw64", hosts=2,
                           overlay=False):
    """CROSS-HOST bytes for one round on an `hosts`-host hive fleet
    (peers split evenly, the pod_launch layout): only frames whose two
    ends sit on different hosts count — intra-host traffic rides the
    hive loopback. Frame sizes come from the same real encoders as
    wire_round_bytes; host-crossing fractions are the even-spread
    estimate ((hosts−1)/hosts of a uniform fan-out crosses).

    overlay=True prices the aggregation tree (docs/OVERLAY.md): verify
    traffic is unchanged (point-to-point by design); secure-agg share
    fan-out collapses to one aggregate per (subtree, miner); plain-mode
    update fan-out crosses once per remote miner-holding subtree and
    the block broadcast once per remote subtree instead of once per
    remote peer."""
    verify, submit, block = _round_frame_bytes(cfg, w64, accepted,
                                               codec=codec)
    n = cfg.num_nodes
    n_s = cfg.num_samples
    m = cfg.num_miners
    v = cfg.num_verifiers
    h = max(1, int(hosts))
    remote_frac = (h - 1) / h
    if not overlay:
        return int(remote_frac * (n_s * (v * verify + m * submit)
                                  + (n - 1) * block))
    cross = remote_frac * n_s * v * verify  # verdict traffic: unchanged
    if cfg.secure_agg:
        # offers ride loopback; one aggregate (≈ one submit frame — the
        # summed tensors have identical shapes) per (subtree, miner)
        cross += remote_frac * h * m * submit
    else:
        # one relayed copy per remote host holding >= 1 miner
        cross += n_s * min(m, h - 1) * submit
    cross += (h - 1) * block  # one block crossing per remote subtree
    return int(cross)


def bench_config(name, cfg, peak, device_iters=10, metrics=None):
    import jax
    import numpy as np

    from biscotti_tpu.crypto import commitments as cm
    from biscotti_tpu.ops import secretshare as ss
    from biscotti_tpu.parallel.sim import Simulator

    _progress(f"{name}: building simulator")
    # NB: bench drives round_step() directly, so the registry feeds the
    # bench-level biscotti_bench_* families below, not Simulator.run()'s
    # per-round instrumentation (that is the sim CLI's --metrics-out)
    sim = Simulator(cfg)
    w, stake = sim.init_state()
    _progress(f"{name}: compiling device round")

    # --- device round: all peers' SGD + noise + defense + aggregation
    for it in range(2):
        w, stake, mask, err = sim.round_step(w, stake, it)
    jax.block_until_ready(w)
    t0 = time.perf_counter()
    for it in range(2, 2 + device_iters):
        w, stake, mask, err = sim.round_step(w, stake, it)
    jax.block_until_ready(w)
    device_s = (time.perf_counter() - t0) / device_iters
    _progress(f"{name}: device round {device_s:.4f}s; measuring host crypto")
    accepted = int(np.asarray(mask).sum())

    d = sim.num_params
    k = cfg.poly_size
    total_shares = cfg.total_shares
    per_miner = cfg.shares_per_miner
    # device-round FLOP estimate for the MFU column: per-contributor SGD
    # fwd+bwd ≈ 6·batch·params (dense-layer lower bound — conv layers
    # reuse weights, so CNN rows undercount), Krum's pairwise-distance
    # matmul 2·n²·d, aggregation n·d
    n_s = cfg.num_samples
    flops = (6.0 * cfg.batch_size * d * n_s
             + (2.0 * n_s * n_s * d if cfg.defense.value == "KRUM" else 0)
             + n_s * d)
    row = {
        "dataset": cfg.dataset, "nodes": cfg.num_nodes, "params": d,
        "defense": cfg.defense.value, "secure_agg": cfg.secure_agg,
        "noising": cfg.noising, "poison": cfg.poison_fraction,
        "device_round_s": round(device_s, 6),
        "device_gflops_est": round(flops / 1e9, 3),
        # fraction of this chip's bf16 peak the device round achieves —
        # see PEAK_FLOPS_BF16 note for why this is honestly tiny
        "mfu": round(flops / max(device_s, 1e-9) / peak, 8),
        "accepted_per_round": accepted,
        "final_error": round(float(err), 4),
    }

    # --- host crypto, measured per-op then composed into the critical path
    delta = np.asarray(w, np.float64)  # representative d-vector
    scale = 10.0 ** cfg.precision
    q = np.trunc(delta * scale).astype(np.int64)
    # CNN-sized models: one timed repetition is enough (each crypto pass is
    # seconds long and variance is low) — keeps the whole 5-config bench
    # inside a driver-friendly wall-clock budget
    reps = 1 if d > 20_000 else 2
    if cfg.secure_agg:
        c = ss.num_chunks(d, k)
        padded = np.zeros(c * k, np.int64)
        padded[:d] = q
        chunks = padded.reshape(c, k)
        xs_all = [i - ss.SHARE_OFFSET for i in range(total_shares)]

        comms = br = sh = None

        def worker():
            nonlocal comms, br, sh
            comms, blinds = cm.vss_commit_chunks(chunks, b"bench-seed" * 3,
                                                 b"ctx")
            br = cm.vss_blind_rows(blinds, xs_all)
            sh = np.asarray(ss.make_shares(q, k, total_shares))

        worker_s = _timeit(worker, warm=1, iters=reps)
        sl = slice(0, per_miner)
        intake = max(1, cfg.num_samples // 2)

        # miner cost, PIPELINED engine (cfg.pipeline + cfg.batch_intake,
        # the runtime's shipping configuration for this bench): arriving
        # share slices fold into the round's VSS accumulator as they
        # land (`fold` — amortized against the intake network window,
        # off the mint path), and mint time pays ONLY the RLC settle —
        # one C·k-point MSM + the lhs comb (VssIntakeBatch.verify).
        c_chunks = ss.num_chunks(d, k)

        def fold_intake():
            acc = cm.VssIntakeBatch(per_miner, c_chunks, k)
            for sidx in range(intake):
                acc.add(sidx, comms, sh[sl], br[sl])
            acc.fold()
            return acc

        t0 = time.perf_counter()
        accs = [fold_intake() for _ in range(reps)]
        fold_s = (time.perf_counter() - t0) / reps
        assert accs[0].verify(xs_all[sl]), "intake settle failed"  # + warm
        miner_s = _timeit(lambda: accs[0].verify(xs_all[sl]),
                          warm=0, iters=reps)
        # the pre-pipeline lump (one-shot vss_verify_multi over the whole
        # intake at mint) — kept for trajectory continuity with
        # BENCH_r02–r05, whose miner_crypto_s was exactly this
        instances = [(comms, xs_all[sl], sh[sl], br[sl])] * intake
        oneshot_s = _timeit(lambda: cm.vss_verify_multi(instances),
                            warm=0, iters=reps)

        # recovery (+ correctness: the int64 pipeline round-trips exactly)
        agg = np.asarray(ss.aggregate_shares(sh[None].repeat(3, axis=0)))
        xs_arr = np.asarray(ss.share_xs(total_shares))

        def recover():
            return np.asarray(ss.recover_update(agg, xs_arr, d, k,
                                                cfg.precision))

        recover_s = _timeit(recover, warm=1, iters=reps)
        rec = recover()
        roundtrip_ok = bool(np.allclose(rec, 3 * q / scale, atol=1e-9))

        # --- accelerator-resident crypto (ISSUE 13, --device-crypto):
        # the SAME mint-time settle with the kernel plane armed
        # (miner_crypto_device_s), plus the device MSM throughput at
        # this config's grid width (msm_points_per_s). Gated by
        # availability (the backend's compiler must accept the kernels;
        # where it refuses, the row says why) and dimensionality
        # (BISCOTTI_BENCH_DEVICE_MAX_D prices out CNN-sized grids).
        from biscotti_tpu.crypto import kernels as dk

        device_cap = int(os.environ.get("BISCOTTI_BENCH_DEVICE_MAX_D",
                                        "2048"))
        if not dk.available():
            row["miner_crypto_device"] = (
                f"skipped: {dk.availability_reason()}")
        elif c_chunks * k <= device_cap:
            dk.set_enabled(True)
            try:
                acc_dev = fold_intake()
                assert acc_dev.verify(xs_all[sl]), "device settle failed"
                if acc_dev._acc_dev is None:
                    # a device fault failed the batch over to CPU
                    # (VssIntakeBatch._device_failover): recording the
                    # CPU settle as a device number would be a lie
                    row["miner_crypto_device"] = {
                        "error": "device settle failed over to the CPU"}
                else:
                    dev_s = _timeit(lambda: acc_dev.verify(xs_all[sl]),
                                    warm=0, iters=reps)
                    row["miner_crypto_device_s"] = round(dev_s, 4)
                    n_pts = c_chunks * k
                    # RLC-shaped odd ~128-bit scalars (the ladder's cost
                    # is scalar-width independent; match the lhs shape)
                    gammas = [((i + 3)
                               * 0x9E3779B97F4A7C15F39CC0605CEDC835) | 1
                              for i in range(n_pts)]
                    msm_t = _timeit(
                        lambda: dk.msm(gammas, acc_dev._acc_dev),
                        warm=1, iters=reps)
                    row["msm_points_per_s"] = round(
                        n_pts / max(msm_t, 1e-9))
            finally:
                dk.set_enabled(False)
        row.update({
            "worker_crypto_s": round(worker_s, 4),
            "miner_intake": intake,
            # mint-critical-path miner crypto under the pipelined engine
            # (intake folded on arrival; this is the settle)
            "miner_crypto_s": round(miner_s, 4),
            # amortized intake-fold budget for the WHOLE intake (runs on
            # the miner host during the round's network window)
            "miner_fold_s": round(fold_s, 4),
            # the pre-pipeline whole-intake lump (r02–r05 comparison row)
            "miner_crypto_oneshot_s": round(oneshot_s, 4),
            "recovery_s": round(recover_s, 4),
            "share_pipeline_roundtrip_ok": roundtrip_ok,
        })
        # serial composition, definitionally unchanged from r02–r05
        # (device + worker + one-shot miner lump + recovery)
        total = device_s + worker_s + oneshot_s + recover_s
        # pipelined composition (one peer per host, depth-1 overlap):
        # device SGD, worker crypto, and the miner's intake folding run
        # CONCURRENTLY on different hosts during the round window; the
        # serialized tail between intake-complete and block broadcast is
        # the settle + recovery. Steady-state s/iter = slowest
        # overlapped stage + the serialized mint tail.
        total_pipe = (max(device_s, worker_s, fold_s)
                      + miner_s + recover_s)
        row["round_total_pipelined_s"] = round(total_pipe, 4)
    else:
        # plain mode: hash commitment + miner recompute — negligible but
        # measured anyway
        import hashlib

        commit_s = _timeit(lambda: hashlib.sha256(q.tobytes()).digest(),
                           warm=1, iters=5)
        row.update({"worker_crypto_s": round(commit_s, 6),
                    "miner_crypto_s": round(commit_s * cfg.num_samples, 6)})
        total = device_s + commit_s * (1 + cfg.num_samples)
        row["round_total_pipelined_s"] = round(
            max(device_s, commit_s) + commit_s * cfg.num_samples, 4)

    row["round_total_s"] = round(total, 4)
    # --- wire data plane: cluster gossip bytes for one round, from the
    # REAL frame encoders (see wire_round_bytes) — raw64 vs the f32+zlib
    # operating point, so BENCH_*.json tracks communication, not just
    # compute (ISSUE 4; NET-SA's bottleneck axis)
    wire_raw = wire_round_bytes(cfg, delta, accepted, codec="raw64")
    wire_f32z = wire_round_bytes(cfg, delta, accepted, codec="f32+zlib")
    # overlay headline row (docs/OVERLAY.md): TCP-crossing bytes/round on
    # a 2-host hive fleet, flat fan-out vs the aggregation tree — the
    # claim is read straight off the artifact instead of hand-derived
    xh_flat = cross_host_round_bytes(cfg, delta, accepted, hosts=2,
                                     overlay=False)
    xh_overlay = cross_host_round_bytes(cfg, delta, accepted, hosts=2,
                                        overlay=True)
    row.update({
        "wire_bytes_per_round": wire_raw,
        "wire_bytes_per_round_f32_zlib": wire_f32z,
        "wire_compression_x": round(wire_raw / max(1, wire_f32z), 2),
        "cross_host_bytes_per_round": xh_flat,
        "cross_host_bytes_per_round_overlay": xh_overlay,
        "overlay_cross_host_saving_x": round(
            xh_flat / max(1, xh_overlay), 2),
    })
    if metrics is not None:
        g = metrics.gauge("biscotti_bench_wire_bytes_per_round",
                          "bench cluster gossip bytes per round")
        g.set(wire_raw, config=name, codec="raw64")
        g.set(wire_f32z, config=name, codec="f32+zlib")
        gx = metrics.gauge(
            "biscotti_bench_cross_host_bytes_per_round",
            "bench TCP-crossing bytes per round on a 2-host hive fleet")
        gx.set(xh_flat, config=name, overlay="off")
        gx.set(xh_overlay, config=name, overlay="on")
    if metrics is not None:
        # every component lands on the telemetry plane too, as one
        # histogram family labeled (config, phase) — rendered to
        # eval/results/bench_metrics.prom at the end of the run
        hist = metrics.histogram("biscotti_bench_phase_seconds",
                                 "bench critical-path component times")
        for phase_key, src in (("device_round", "device_round_s"),
                               ("worker_crypto", "worker_crypto_s"),
                               ("miner_crypto", "miner_crypto_s"),
                               ("miner_fold", "miner_fold_s"),
                               ("recovery", "recovery_s")):
            if src in row:
                hist.observe(row[src], config=name, phase=phase_key)
        metrics.gauge("biscotti_bench_round_total_seconds",
                      "bench crypto-inclusive s/iter").set(total, config=name)
        metrics.gauge(
            "biscotti_bench_round_pipelined_seconds",
            "bench crypto-inclusive s/iter, pipelined composition").set(
            row["round_total_pipelined_s"], config=name)
    _progress(f"{name}: serial {total:.3f}s/iter, "
              f"pipelined {row['round_total_pipelined_s']:.3f}s/iter")
    return name, row, total


def bench_peer_density(sizes=(100, 400, 1000), iterations=2,
                       budget_s=900.0):
    """Scale-frontier entry (ISSUE 9): LIVE hive-hosted clusters at
    N ∈ {100, 400, 1000} — real protocol rounds over the loopback
    transport with the batched device plane (runtime/hive.py), not a
    simulator row. Reports s/iter, peak RSS per co-hosted peer, and the
    chain-equality verdict, so BENCH_r*.json tracks the density frontier
    alongside the flagship round time. Each size runs as a subprocess
    (its RSS peak must be its own, not the bench driver's), on whatever
    device JAX gives it — so this section runs BEFORE the parent first
    touches JAX (a chip belongs to one process), one child at a time, and
    each row carries the device its child reported. A failed or timed-out
    size yields an error row, and the bench exits non-zero.

    Set BISCOTTI_BENCH_DENSITY=0 to skip (e.g. memory-constrained CI)."""
    import subprocess

    if os.environ.get("BISCOTTI_BENCH_DENSITY", "1") == "0":
        return {"skipped": "BISCOTTI_BENCH_DENSITY=0"}
    out = {}
    deadline = time.time() + budget_s
    for n in sizes:
        name = f"n{n}"
        budget = deadline - time.time()
        if budget < 30.0:
            out[name] = {"error": "density budget exhausted"}
            continue
        _progress(f"peer_density: N={n} live hive "
                  f"({iterations} iterations)")
        cmd = [sys.executable, "-m", "biscotti_tpu.runtime.hive",
               "-t", str(n), "-d", "mnist",
               "--iterations", str(iterations),
               "-sa", "0", "-np", "0", "-vp", "1", "--seed", "3"]
        try:
            proc = subprocess.run(
                cmd, cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=budget)
            # one parser for the hive summary format (pod_launch is the
            # other consumer — shared so the two can't drift)
            from biscotti_tpu.tools.pod_launch import hive_summary

            s = hive_summary(proc.stdout)
            if s is None:
                # died before printing its summary (OOM-kill is the
                # expected failure mode at N=1000): record the exit code
                # and the stderr tail, or the density row is undebuggable
                out[name] = {"error": f"no summary (rc={proc.returncode})",
                             "stderr_tail": proc.stderr[-800:]}
                _progress(f"peer_density: N={n} failed rc="
                          f"{proc.returncode}")
                continue
            out[name] = {
                "peers": s["peers"],
                "blocks": s["blocks"],
                "chains_equal": s["chains_equal_local"],
                "s_per_iter": s["s_per_iter"],
                "rss_peak_mb": round(s["rss_peak_bytes"] / 2**20, 1),
                "rss_per_peer_mb": round(
                    s["rss_per_peer_bytes"] / 2**20, 2),
                "loop_lag_s": s["loop_lag_s"],
                "platform": s["platform"],
                "device_kind": s["device_kind"],
                "device_count": s["device_count"],
                "devices_used": s["devices_used"],
            }
            _progress(f"peer_density: N={n} {s['s_per_iter']}s/iter, "
                      f"{out[name]['rss_per_peer_mb']}MB/peer, "
                      f"chains_equal={s['chains_equal_local']}")
        except Exception as e:
            out[name] = {"error": f"{type(e).__name__}: {e}"}
            _progress(f"peer_density: N={n} failed: {out[name]['error']}")
    return out


def bench_crypto_kernel(widths=(8, 35, 100)):
    """Device-crypto microbench (ISSUE 13): CPU vs device MSM across
    intake widths — the RLC lhs Σγᵢ·Cᵢ shape whose width is the number
    of commitments a miner batched. Reports per-width seconds and
    points/s for both paths (device timings are steady-state: one warm
    call absorbs the per-shape XLA compile), so the BENCH artifact shows
    device MSM throughput scaling with intake width. The per-config
    `miner_crypto_device_s` / `msm_points_per_s` keys in the main table
    carry the same story at each config's full grid dimensionality.

    Set BISCOTTI_BENCH_CRYPTO_KERNEL=0 to skip."""
    if os.environ.get("BISCOTTI_BENCH_CRYPTO_KERNEL", "1") == "0":
        return {"skipped": "BISCOTTI_BENCH_CRYPTO_KERNEL=0"}
    from biscotti_tpu.crypto import commitments as cm
    from biscotti_tpu.crypto import ed25519 as ed
    from biscotti_tpu.crypto import kernels as dk

    if not dk.available():
        return {"skipped": dk.availability_reason()}
    _progress(f"crypto_kernel: CPU vs device MSM at widths {widths}")
    key = cm.CommitKey.generate(max(widths), label=b"bench-msm")
    out = {}
    for w in widths:
        pts = key.points[:w]
        scalars = [((i + 3) * 0x9E3779B97F4A7C15F39CC0605CEDC835) | 1
                   for i in range(w)]
        # the parity check reuses the timed runs' last results — no
        # extra MSM just to compare
        res = {}
        cpu_s = _timeit(lambda: res.__setitem__("cpu",
                                                cm.msm(scalars, pts)),
                        warm=1, iters=3)
        dev_s = _timeit(lambda: res.__setitem__("dev",
                                                dk.msm(scalars, pts)),
                        warm=1, iters=3)
        ok = ed.point_equal(res["cpu"], res["dev"])
        out[f"w{w}"] = {
            "cpu_msm_s": round(cpu_s, 5),
            "device_msm_s": round(dev_s, 5),
            "cpu_msm_points_per_s": round(w / max(cpu_s, 1e-9)),
            "device_msm_points_per_s": round(w / max(dev_s, 1e-9)),
            "results_equal": bool(ok),
        }
        _progress(f"crypto_kernel: w={w} cpu {cpu_s:.4f}s "
                  f"device {dev_s:.4f}s equal={bool(ok)}")
    return out


def bench_straggler_degradation(n=10, rounds=3, budget_s=600.0):
    """Straggler-degradation entry (ISSUE 10): LIVE mnist clusters with
    0% / 10% / 20% of peers on a seeded 4x compute-slowdown profile
    (runtime/faults.FaultPlan slow kind), fixed vs adaptive deadlines —
    the mean-round-time degradation curve the straggler-tolerance plane
    exists to flatten, tracked across PRs in the BENCH artifact. Runs
    in-process (the chaos harness pattern): secure-agg + verification on
    so the slowed paths (SGD + worker/miner crypto) actually carry the
    round, rounds measured off the anchor's per-iteration log stamps.

    Set BISCOTTI_BENCH_STRAGGLER=0 to skip."""
    import asyncio

    if os.environ.get("BISCOTTI_BENCH_STRAGGLER", "1") == "0":
        return {"skipped": "BISCOTTI_BENCH_STRAGGLER=0"}

    from biscotti_tpu.config import BiscottiConfig, Timeouts
    from biscotti_tpu.runtime.faults import FaultPlan
    from biscotti_tpu.runtime.peer import PeerAgent
    from biscotti_tpu.tools.chaos import chain_oracle

    fast = Timeouts(update_s=12.0, block_s=30.0, krum_s=5.0, share_s=12.0,
                    rpc_s=8.0)

    def plan_for(frac):
        """Seeded plan drawing EXACTLY round(frac*n) slow peers: the
        per-node draw is probabilistic, so scan seeds for the one whose
        table hits the target count — deterministic once found, and the
        chosen seed rides into the artifact for replay."""
        want = int(round(frac * n))
        if want == 0:
            return FaultPlan(), 0
        for seed in range(500):
            p = FaultPlan(seed=seed, slow=frac, slow_factor=4.0)
            if len(p.slow_table(n)) == want:
                return p, seed
        # no seed hit the exact count (tiny n edge): pin node 1
        return FaultPlan(slow_node=1, slow_factor=4.0), -1

    def run_case(plan, adaptive, port):
        def cfg(i):
            return BiscottiConfig(
                node_id=i, num_nodes=n, dataset="mnist", base_port=port,
                num_verifiers=1, num_miners=1, num_noisers=1,
                secure_agg=True, noising=False, verification=True,
                max_iterations=rounds, convergence_error=0.0,
                sample_percent=1.0, batch_size=10, timeouts=fast, seed=3,
                fault_plan=plan, adaptive_deadlines=adaptive)

        async def go():
            agents = [PeerAgent(cfg(i)) for i in range(n)]
            return await asyncio.gather(*(a.run() for a in agents))

        results = asyncio.run(go())
        eq, _, real = chain_oracle(results)
        stamps = [float(x.split(",")[2]) for x in results[0]["logs"]]
        mean_round = ((stamps[-1] - stamps[0]) / (len(stamps) - 1)
                      if len(stamps) >= 2 else None)
        excluded = sum(
            sum((r["telemetry"]["stragglers"]["excluded"] or {}).values())
            for r in results)
        return {"mean_round_s": (round(mean_round, 4)
                                 if mean_round is not None else None),
                "chains_equal": eq, "real_blocks": real,
                "straggler_excluded": excluded}

    out = {}
    deadline = time.time() + budget_s
    # listen ports BELOW the box's ephemeral range (16000+ here): an
    # earlier case's lingering outbound socket can otherwise squat the
    # next case's listen port (the documented cross-cluster bind flake)
    port = 14310
    # throwaway warm-up: the FIRST live cluster in the process pays the
    # mnist shard load + XLA compile inside its first round — without
    # this the slow0_fixed baseline absorbs ~20 s of one-time cost and
    # the whole degradation curve reads as an improvement
    _progress("straggler_degradation: warm-up cluster (discarded)")
    try:
        run_case(FaultPlan(), False, port)
        port += n + 3
    except Exception as e:
        _progress(f"straggler_degradation: warm-up failed: {e}")
    for frac in (0.0, 0.10, 0.20):
        plan, seed = plan_for(frac)
        slowed = len(plan.slow_table(n))
        for adaptive in (False, True):
            name = f"slow{int(frac * 100)}_" \
                   f"{'adaptive' if adaptive else 'fixed'}"
            if time.time() > deadline - 30:
                out[name] = {"error": "straggler budget exhausted"}
                continue
            _progress(f"straggler_degradation: {name} "
                      f"({slowed}/{n} peers at 4x)")
            try:
                row = run_case(plan, adaptive, port)
                row.update(slowed_peers=slowed, slow_seed=seed,
                           slow_factor=4.0)
                out[name] = row
                _progress(f"straggler_degradation: {name} "
                          f"{row['mean_round_s']}s/round, "
                          f"chains_equal={row['chains_equal']}")
            except Exception as e:
                out[name] = {"error": f"{type(e).__name__}: {e}"}
                _progress(f"straggler_degradation: {name} failed: "
                          f"{out[name]['error']}")
            port += n + 3
    # the headline ratio: how much a 20% slow fleet degrades the round
    # under fixed vs adaptive deadlines (None until both rows exist)
    base = (out.get("slow0_fixed") or {}).get("mean_round_s")
    for k in ("slow20_fixed", "slow20_adaptive"):
        row = out.get(k) or {}
        if base and row.get("mean_round_s"):
            row["vs_homogeneous"] = round(row["mean_round_s"] / base, 2)
    return out


def bench_attack_matrix(budget_s: float = 600.0):
    """Attack-matrix guard cells (ISSUE 14): the static-vs-adaptive
    poisoner pair under the accept-mask defenses, live at the matrix's
    operating point (eval/eval_attack_matrix.py --quick). The full
    matrix is the eval artifact (eval/results/attack_matrix.json);
    these rows ride the BENCH artifact so `tools/bench_diff` fails
    loudly when a future PR flips a survived cell (`failed` 0 -> 1) or
    lets more poisoned sources through (`accepted_poisoned_n`).

    Set BISCOTTI_BENCH_ATTACK=0 to skip."""
    if os.environ.get("BISCOTTI_BENCH_ATTACK", "1") == "0":
        return {"skipped": "BISCOTTI_BENCH_ATTACK=0"}

    import importlib.util
    from types import SimpleNamespace

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "eval", "eval_attack_matrix.py")
    spec = importlib.util.spec_from_file_location("eval_attack_matrix",
                                                  path)
    am = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(am)

    from biscotti_tpu.config import Defense

    # the matrix driver's default operating point (mnist@dir0.3, 10
    # nodes, 3 verifiers, one seed) — per-cell calls so the budget is
    # enforced BETWEEN cells like every sibling bench entry
    ns = SimpleNamespace(nodes=10, verifiers=3, rounds=8, seed=11,
                         poison=0.3, flood=30, dataset="mnist@dir0.3")
    # hug x ENSEMBLE is THE tentpole guard (ISSUE 16): the adaptive
    # defense plane's claim is exactly this cell flipping to survived —
    # a future PR that un-survives the hugger fails the bench_diff gate
    cells = [("static", Defense.KRUM), ("hug", Defense.KRUM),
             ("static", Defense.FOOLSGOLD), ("hug", Defense.FOOLSGOLD),
             ("hug", Defense.ENSEMBLE)]
    out = {"complete": True}
    deadline = time.time() + budget_s
    port = 14190
    for camp, d in cells:
        name = f"{camp}_{d.value.lower()}"
        if time.time() > deadline - 30:
            out[name] = {"error": "attack-matrix budget exhausted"}
            out["complete"] = False
            continue
        _progress(f"attack_matrix: {name} (live cell)")
        try:
            row = am.run_cell(camp, d, True, port, ns)
            # the survival bits (failed / accepted_poisoned_n) are the
            # regression-gated keys; the live-cluster error is noisy
            # run-to-run (round intake varies with box load), so it
            # rides as `anchor_error` — informational, outside the
            # bench_diff final_error regress pattern
            out[name] = {k: row[k] for k in
                         ("chains_equal", "survived",
                          "failed", "accepted_poisoned_n")}
            out[name]["anchor_error"] = row["final_error"]
            _progress(f"attack_matrix: {name} survived="
                      f"{row['survived']} err={row['final_error']}")
        except Exception as e:
            out[name] = {"error": f"{type(e).__name__}: {e}"}
            out["complete"] = False
            _progress(f"attack_matrix: {name} failed: "
                      f"{out[name]['error']}")
        port += ns.nodes + 2
    return out


def bench_migration(n=100, iterations=2, budget_s=600.0):
    """Migration-cost entry (ISSUE 19): a LIVE two-hive cluster at N=100
    under the placement controller with a rigged hot-host signal so
    every decision point actually moves peers — reporting per-move
    downtime and ticket size (`migration_downtime_s` /
    `migration_bytes`, the two lower-is-better keys tools/bench_diff
    gates). The rig goes through the controller's signals_fn seam: on
    one box the real hive gauges are process-wide, so both hives read
    equally hot and nothing would move — the injection makes the COST
    measurable without faking the decision function itself
    (docs/PLACEMENT.md).

    Set BISCOTTI_BENCH_MIGRATION=0 to skip."""
    if os.environ.get("BISCOTTI_BENCH_MIGRATION", "1") == "0":
        return {"skipped": "BISCOTTI_BENCH_MIGRATION=0"}
    import asyncio

    from biscotti_tpu.config import BiscottiConfig
    from biscotti_tpu.runtime import placement
    from biscotti_tpu.runtime.hive import LoopbackHub
    from biscotti_tpu.runtime.membership import surviving_prefix_oracle
    from biscotti_tpu.runtime.peer import PeerAgent

    _progress(f"migration: N={n} two-hive cluster, rigged hot host")
    plan = placement.PlacementPlan(enabled=True, seed=0, interval=1,
                                   max_moves=2, lag_hot_s=0.05)
    layout = placement.hive_layout(n, 2)
    hive_ids = [f"host{i}" for i in range(len(layout))]
    assignment = {}
    for hid, (start, count) in zip(hive_ids, layout):
        for node in range(start, start + count):
            assignment[node] = hid
    cfg = BiscottiConfig(
        num_nodes=n, dataset="creditcard", base_port=15700,
        num_verifiers=1, num_miners=1, num_noisers=1,
        secure_agg=False, noising=False, verification=False,
        max_iterations=iterations, convergence_error=0.0,
        sample_percent=1.0, batch_size=8, seed=3,
        placement_plan=plan)
    cfg = cfg.replace(timeouts=cfg.timeouts.scaled(
        n, cfg.num_verifiers, cfg.num_miners))
    hubs = {hid: LoopbackHub() for hid in hive_ids}

    def make_agent(node, hive_id, ticket):
        return PeerAgent(cfg.replace(node_id=node), hive=hubs[hive_id],
                         ticket=ticket)

    def rigged_signals(assignment, agents):
        by = {}
        for node, hid in sorted(assignment.items()):
            by.setdefault(hid, []).append(node)
        return [placement.HostSignals(
            hive_id=hid, peers=tuple(nodes),
            loop_lag_s=1.0 if hid == hive_ids[0] else 0.0)
            for hid, nodes in sorted(by.items())]

    ctl = placement.PlacementController(make_agent, assignment, plan,
                                        signals_fn=rigged_signals)
    try:
        results = asyncio.run(asyncio.wait_for(ctl.run(), budget_s))
    except Exception as e:
        return {"error": f"{type(e).__name__}: {e}"}
    equal, settled, real = surviving_prefix_oracle(results)
    moves = len(ctl.moves_applied)
    out = {
        "peers": n, "iterations": iterations, "moves": moves,
        "chains_equal": equal, "settled_height": settled,
        "real_blocks": real,
    }
    if moves:
        out["migration_downtime_s"] = round(
            sum(ctl.downtimes_s) / moves, 4)
        out["downtime_max_s"] = round(max(ctl.downtimes_s), 4)
        out["migration_bytes"] = int(sum(ctl.ticket_bytes) / moves)
        out["ticket_bytes_max"] = max(ctl.ticket_bytes)
    _progress(f"migration: {moves} moves, "
              f"{out.get('migration_downtime_s', '-')}s/move, "
              f"{out.get('migration_bytes', '-')}B/ticket, "
              f"chains_equal={equal}")
    return out


def _errors(node, path=""):
    """Paths of every `error` key in a result tree — any section that
    caught a failure into its row."""
    if not isinstance(node, dict):
        return []
    out = [path or "."] if "error" in node else []
    for k, v in node.items():
        out += _errors(v, f"{path}/{k}" if path else str(k))
    return out


def main():
    # a pure-Python crypto fallback would time the wrong system ~30x slow
    from biscotti_tpu.crypto import _native

    if _native.load_error():
        raise SystemExit(f"bench: native crypto library unavailable: "
                         f"{_native.load_error()}")

    # scale frontier: live hive-hosted peer density (one box, real
    # rounds) — the number the hive runtime exists to move. FIRST: each
    # child process needs the chip, which this process holds from its
    # first JAX call on.
    density = bench_peer_density()

    import jax

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.utils import jaxenv

    jax.config.update("jax_enable_x64", True)
    jaxenv.configure_compile_cache()
    device = jaxenv.device_info()
    peak = peak_flops(device["device_kind"])

    base = dict(batch_size=10, epsilon=1.0, sample_percent=0.70,
                num_verifiers=3, num_miners=3, num_noisers=2, seed=0)
    configs = [
        # BASELINE.json "configs" rows, in order
        ("creditcard_10", BiscottiConfig(
            dataset="creditcard", num_nodes=10, secure_agg=True,
            noising=True, verification=True, defense=Defense.KRUM, **base)),
        ("mnist_100_clean", BiscottiConfig(
            dataset="mnist", num_nodes=100, secure_agg=True, noising=False,
            verification=True, defense=Defense.KRUM, **base)),
        ("mnist_100_poison30_krum", BiscottiConfig(
            dataset="mnist", num_nodes=100, secure_agg=True, noising=True,
            verification=True, defense=Defense.KRUM, poison_fraction=0.30,
            **base)),
        ("mnist_100_dp_eps1", BiscottiConfig(
            dataset="mnist", num_nodes=100, secure_agg=True, noising=True,
            verification=True, defense=Defense.KRUM, **base)),
        ("cifar_lenet_100_krum_secagg", BiscottiConfig(
            dataset="cifar", model_name="cifar_cnn", num_nodes=100,
            secure_agg=True, noising=False, verification=True,
            defense=Defense.KRUM, **base)),
        # remaining reference model families (ML/Pytorch/mnist_cnn_model.py,
        # lfw_cnn_model.py, svm_model.py) — no published fleet numbers, so
        # vs_baseline stays null; rows prove every family runs the full
        # crypto-inclusive round at reference dimensions
        ("mnist_cnn_100_krum_secagg", BiscottiConfig(
            dataset="mnist", model_name="mnist_cnn", num_nodes=100,
            secure_agg=True, noising=False, verification=True,
            defense=Defense.KRUM, **base)),
        ("lfw_cnn_100_krum_secagg", BiscottiConfig(
            dataset="lfw", model_name="lfw_cnn", num_nodes=100,
            secure_agg=True, noising=False, verification=True,
            defense=Defense.KRUM, **base)),
        ("svm_mnist_100_krum_secagg", BiscottiConfig(
            dataset="mnist", model_name="svm", num_nodes=100,
            secure_agg=True, noising=False, verification=True,
            defense=Defense.KRUM, **base)),
    ]

    from biscotti_tpu.telemetry import MetricsRegistry

    registry = MetricsRegistry(max_label_sets=256)  # 8 configs × phases
    rows = {}
    headline_total = None
    for name, cfg in configs:
        iters = 4 if cfg.model_name else 10  # CNN/svm rows: fewer reps
        try:
            name, row, total = bench_config(name, cfg, peak,
                                            device_iters=iters,
                                            metrics=registry)
        except Exception as e:
            # the other configs still run and report; the error row
            # makes the whole bench exit non-zero (see _errors)
            traceback.print_exc()
            rows[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        # only the mnist SOFTMAX rows compare against the reference's 38.2
        # s/iter fleet number (same model family); cnn/svm/lfw rows have no
        # published counterpart
        if name.startswith("mnist_100"):
            row["vs_baseline"] = round(BASELINE_MNIST_S_PER_ITER / total, 2)
        else:
            row["vs_baseline"] = None  # reference published no number
        rows[name] = row
        if name == "mnist_100_dp_eps1":
            # headline = the PIPELINED engine's steady-state s/iter (the
            # runtime this PR ships); the serial composition stays in the
            # row as round_total_s for the r02–r05 trajectory
            headline_total = row["round_total_pipelined_s"]

    # straggler-degradation curve (ISSUE 10): live mnist round time at
    # 0/10/20% slowed peers, fixed vs adaptive deadlines
    straggler = bench_straggler_degradation()

    # attack-matrix guard cells (ISSUE 14): static vs adaptive poisoner
    # under the accept-mask defenses — bench_diff fails loudly when a
    # survived cell flips
    attack_matrix = bench_attack_matrix()

    # migration-cost entry (ISSUE 19): per-move downtime + ticket bytes
    # through the live placement controller at N=100 — the two
    # lower-is-better keys bench_diff gates for the elastic fleet plane
    migration = bench_migration()

    # device-crypto microbench (ISSUE 13): CPU vs device MSM across
    # intake widths {8, 35, 100} — the scaling evidence for the
    # accelerator-resident crypto plane
    crypto_kernel = bench_crypto_kernel()
    if registry is not None and isinstance(crypto_kernel, dict):
        msm_gauge = registry.gauge(
            "biscotti_bench_msm_points_per_s",
            "bench MSM throughput by path across intake widths")
        for wname, r in crypto_kernel.items():
            if isinstance(r, dict) and "cpu_msm_points_per_s" in r:
                msm_gauge.set(r["cpu_msm_points_per_s"], width=wname,
                              path="cpu")
                msm_gauge.set(r["device_msm_points_per_s"], width=wname,
                              path="device")

    detail = {
        **device,
        "data_note": ("synthetic Gaussian shards at reference dimensions "
                      "(zero-egress env): timings comparable, error columns "
                      "not"),
        "configs": rows,
        "peer_density": density,
        "straggler_degradation": straggler,
        "attack_matrix": attack_matrix,
        "migration": migration,
        "crypto_kernel": crypto_kernel,
    }
    # Full per-config detail goes to a file + stderr; stdout carries exactly
    # ONE compact JSON line so the driver's parser always succeeds
    # (BENCH_r02 "parsed": null was the oversized inline line).
    detail_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "eval", "results", "bench_detail.json")
    try:
        os.makedirs(os.path.dirname(detail_path), exist_ok=True)
        with open(detail_path, "w") as f:
            json.dump(detail, f, indent=1)
        _progress(f"per-config detail written to {detail_path}")
        # the same numbers in Prometheus text form, for dashboard ingest
        prom_path = os.path.join(os.path.dirname(detail_path),
                                 "bench_metrics.prom")
        with open(prom_path, "w") as f:
            f.write(registry.render())
        _progress(f"telemetry page written to {prom_path}")
    except OSError as e:
        _progress(f"could not write detail file: {e}")
    print(json.dumps(detail), file=sys.stderr, flush=True)
    serial_total = rows.get("mnist_100_dp_eps1", {}).get("round_total_s")
    errors = _errors(detail)
    out = {
        **device,
        # sections whose row holds an `error`: non-empty => exit code 1
        "errors": errors,
        "metric": ("crypto-inclusive s/iter, 100-peer MNIST softmax + Krum "
                   "+ DP eps=1.0 + secure-agg, pipelined round engine "
                   "(ref fleet: 38.2 s/iter)"),
        "value": round(headline_total, 4) if headline_total else None,
        "unit": "s/iter",
        # the pipelined value composes MEASURED components under the
        # depth-1 one-peer-per-host overlap model (see bench_config);
        # the serial sum of the same components rides along so the
        # modeled number never stands alone
        "serial_s_per_iter": serial_total,
        "vs_baseline": (round(BASELINE_MNIST_S_PER_ITER / headline_total, 2)
                        if headline_total else None),
        # live peer-density frontier (hive runtime, runtime/hive.py):
        # s/iter + per-peer RSS at N ∈ {100,400,1000} co-hosted on this
        # box, chains verified equal — tracks the scale wall, not just
        # the flagship round
        "peer_density": density,
        # straggler-degradation curve (runtime/stragglers.py): live
        # mnist mean round time at 0/10/20% peers on the 4x slow
        # profile, fixed vs adaptive deadlines — the robustness number
        # the straggler-tolerance plane exists to move
        "straggler_degradation": straggler,
        # attack-matrix guard cells (runtime/adversary.py): survival +
        # accepted-poison bits for the static/hug x KRUM/FOOLSGOLD
        # cells — a flipped survived cell is a bench_diff regression
        # (docs/ADVERSARY.md; full matrix in eval/results/)
        "attack_matrix": attack_matrix,
        # migration cost (runtime/placement.py): mean per-move downtime
        # + ticket bytes through the live controller at N=100 — a PR
        # that makes moves slower or tickets fatter is a bench_diff
        # regression (docs/PLACEMENT.md)
        "migration": migration,
        # device-crypto microbench (crypto/kernels): CPU vs device MSM
        # across intake widths — the scaling evidence behind
        # --device-crypto (docs/CRYPTO_KERNELS.md)
        "crypto_kernel": crypto_kernel,
    }
    print(json.dumps(out))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
