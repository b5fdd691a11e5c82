#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py        # on a machine with a TPU; one process

Drives the main path once through the entry points a user calls
(`parallel.sim.Simulator`, `runtime.hive.main`) at the full width of the
largest model in the zoo (mnist_cnn, d = 164,266), checks every result by
the repo's own oracles, and prints two JSON lines last: the summary (cache
directory, compile seconds, per-phase detail, `"claim": null`), then the
driver's result line, which holds exactly
`{"ok": true, "device": {"platform", "kind", "count"}}` and nothing else.
Any failed check raises: no phase is wrapped in try/except, so a traceback
and a non-zero exit are the failure report and there is no result line.

Refuses to start unless `jax.default_backend() == "tpu"`: nothing here is
meaningful on the CPU (the CPU suite is `pytest tests/`, and
`tests/test_tpu_lowering.py` is the cheap pre-flight that compiles for the
v5e without one). One process: the chip belongs to whoever touched JAX
first, so nothing here spawns a child that needs it.

Phases (each reports wall seconds, seconds spent compiling or fetching
programs, and the programs built inside its timed window, which must be 0):

  device_round   Simulator, mnist_cnn, N=100, 70 sampled, KRUM, DP eps=1:
                 2 warm-up + 5 timed round_steps.
  hive           runtime.hive.main: 32 live peers, mnist_cnn, secure-agg +
                 noising + verification, 4 iterations, SGD batched on chip.
  pallas         Simulator, mnist, N=1024 (716 contributors): the Mosaic
                 Krum kernel inside the round under x64, then Pallas vs XLA
                 vs a float64 numpy oracle on the round's own noised deltas;
                 then n=4096 at d=164,266 for the VMEM ceiling.
  device_crypto  what `kernels.active()` says equals what ran: the plane
                 runs on the device, or arming it is refused with the
                 compiler's reason and the CPU settles.
  multichip      only with more than one device: the sharded round step and
                 the hive's batched plane over all of them.
"""

import contextlib
import io
import json
import sys
import time

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    """assert that survives `python -O`."""
    if not cond:
        raise SmokeFailure(msg)


def note(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


class CompileMeter:
    """What JAX built: one (epoch seconds, duration) entry per program it
    had to produce — compiled afresh or fetched from the persistent cache —
    plus the cache's own hit and miss counts."""

    def __init__(self):
        import jax

        self.events = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.events.append((time.time(), duration))

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return len(self.events), self.hits, self.misses

    def since(self, mark):
        n, hits, misses = mark
        return {"compile_s": round(sum(d for _, d in self.events[n:]), 3),
                "programs": len(self.events) - n,
                "cache_hits": self.hits - hits,
                "cache_misses": self.misses - misses}

    def between(self, t0, t1):
        return sum(1 for t, _ in self.events if t0 < t <= t1)


def krum_oracle(x, f):
    """float64 numpy Krum (ops/krum.py semantics): scores = sum of the
    n-f-2 smallest squared distances to others; accept the n-f lowest."""
    import numpy as np

    x = np.asarray(x, np.float64)
    n = x.shape[0]
    sq = np.sum(x * x, axis=1)
    d = np.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
    np.fill_diagonal(d, np.inf)
    scores = np.sort(d, axis=1)[:, :max(n - f - 2, 0)].sum(axis=1)
    accept = np.zeros(n, bool)
    accept[np.argsort(scores, kind="stable")[:n - f]] = True
    return scores, accept


# ------------------------------------------------------------------ phases


def phase_device_round(meter, nodes=100, model="mnist_cnn", params=164_266,
                       warm=2, timed=5):
    import jax
    import numpy as np

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator

    # bench.py's mnist_cnn_100_krum_secagg row, with noising on
    cfg = BiscottiConfig(
        dataset="mnist", model_name=model, num_nodes=nodes, secure_agg=True,
        noising=True, verification=True, defense=Defense.KRUM,
        batch_size=10, epsilon=1.0, sample_percent=0.70, num_verifiers=3,
        num_miners=3, num_noisers=2, seed=0)
    sim = Simulator(cfg)
    check(sim.num_params == params, f"d = {sim.num_params}, not {params}")
    w, stake = sim.init_state()
    for it in range(warm):
        w, stake, mask, err = sim.round_step(w, stake, it)
    jax.block_until_ready(w)
    mark = meter.mark()
    t0 = time.perf_counter()
    for it in range(warm, warm + timed):
        w, stake, mask, err = sim.round_step(w, stake, it)
    jax.block_until_ready(w)
    round_s = (time.perf_counter() - t0) / timed
    window = meter.since(mark)

    s = cfg.num_samples
    accepted = int(np.asarray(mask).sum())
    check(np.asarray(w).shape == (params,), "w has the wrong shape")
    check(bool(np.all(np.isfinite(np.asarray(w)))), "w is not finite")
    check(np.isfinite(float(err)), "test error is not finite")
    check(accepted == s - s // 2,
          f"accepted {accepted}, expected {s} - {s // 2}")
    check(window["programs"] == 0,
          f"{window['programs']} programs built inside the timed window")
    return {"round_s": round(round_s, 5), "params": sim.num_params,
            "contributors": s, "accepted": accepted,
            "test_error": round(float(err), 4),
            "compilations_after_warmup": window["programs"]}


def run_hive(argv):
    """runtime.hive.main(argv) with its stdout captured: (exit code,
    summary dict, count of non-empty blocks in the anchor's chain dump)."""
    from biscotti_tpu.runtime import hive
    from biscotti_tpu.tools.pod_launch import hive_summary

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = hive.main([*argv, "--dump-chain"])
    out = buf.getvalue()
    blocks = [ln for ln in out.splitlines()
              if ln.startswith("iter=") and not ln.startswith("iter=-1")]
    minted = sum(1 for ln in blocks if "ndeltas=0" not in ln)
    return rc, hive_summary(out), minted


def phase_hive(meter, device, peers=32, model="mnist_cnn", iterations=4):
    from biscotti_tpu.crypto import _native

    rc, s, minted = run_hive(
        ["-t", str(peers), "-d", "mnist", "--model", model,
         "--iterations", str(iterations), "-sa", "1", "-np", "1",
         "-vp", "1"])
    check(rc == 0, f"hive.main exited {rc}")
    check(s is not None, "hive.main printed no summary")
    check(s["chains_equal_local"], "co-hosted chains differ")
    check(minted >= iterations - 1,
          f"only {minted} non-empty blocks in {iterations} iterations")
    check(s["batch_device"] is True,
          f"SGD did not run batched: {s['batch_fallback']}")
    check(s["sgd_batches"] >= iterations,
          f"{s['sgd_batches']} batched SGD dispatches")
    check((s["platform"], s["device_kind"]) ==
          (device["platform"], device["device_kind"]),
          f"hive ran on {s['platform']}/{s['device_kind']}")
    check(_native.load_error() == "",
          f"native crypto library not loaded: {_native.load_error()}")
    stamps = s["iter_stamps"]
    late = meter.between(stamps[0], stamps[-1])
    check(late == 0,
          f"{late} programs built after the hive's first iteration")
    return {"peers": s["peers"], "blocks": s["blocks"],
            "non_empty_blocks": minted, "s_per_iter": s["s_per_iter"],
            "sgd_batches": s["sgd_batches"],
            "devices_used": s["devices_used"],
            "final_error": s["final_error"],
            "compilations_after_warmup": late}


def phase_pallas(nodes=1024, big=(4096, 164_266)):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.ops.krum import krum_accept_mask, krum_scores
    from biscotti_tpu.ops.krum_pallas import (PALLAS_MAX_N, PALLAS_MIN_N,
                                              krum_scores_pallas)
    from biscotti_tpu.parallel.sim import Simulator

    cfg = BiscottiConfig(
        dataset="mnist", num_nodes=nodes, noising=True, verification=True,
        defense=Defense.KRUM, batch_size=10, epsilon=1.0,
        sample_percent=0.70, seed=0)
    s, f = cfg.num_samples, cfg.num_samples // 2
    check(PALLAS_MIN_N <= s <= PALLAS_MAX_N,
          f"{s} contributors fall outside the kernel's window")
    sim = Simulator(cfg)
    seed = jnp.asarray(cfg.seed, jnp.int32)
    w, stake = sim.init_state()
    hlo = jax.jit(sim._round_step_raw).lower(
        w, stake, 0, seed, sim.x, sim.y, sim.x_val, sim.y_val).as_text()
    check("tpu_custom_call" in hlo,
          "the round step lowered without the Mosaic custom call")
    noised = sim.noised_updates(w, 0)  # before round_step donates w
    check(noised.shape == (s, sim.num_params), f"noised {noised.shape}")
    w1, _, mask, err = sim.round_step(w, stake, 0)
    jax.block_until_ready(w1)
    check(bool(jnp.all(jnp.isfinite(w1))), "w is not finite")
    check(int(mask.sum()) == s - f, f"round accepted {int(mask.sum())}")

    # the same [S, d] through the kernel, the XLA path and the oracle
    got_p = np.asarray(krum_scores_pallas(noised, f), np.float64)
    got_x = np.asarray(krum_scores(noised, f), np.float64)
    ref, ref_accept = krum_oracle(noised, f)

    def rel(a, b):
        return float(np.max(np.abs(a - b) / np.abs(b)))

    tol_p, tol_x = rel(got_p, ref), rel(got_x, ref)
    dev_accept = np.asarray(krum_accept_mask(noised, f))
    # a disagreement is a tie when the oracle's own score sits within the
    # device's measured score error of the accept/reject cut
    order = np.sort(ref)
    cut = 0.5 * (order[s - f - 1] + order[s - f])
    differ = np.nonzero(dev_accept != ref_accept)[0]
    beyond = [int(i) for i in differ
              if abs(ref[i] - cut) > 2.0 * tol_p * cut]
    # seen on the v5e at default matmul precision (PR 21): 4.1e-6 for
    # both paths against the oracle, 2.3e-7 between them
    check(tol_p < 2e-5 and tol_x < 2e-5,
          f"Krum scores off the float64 oracle: pallas {tol_p:.2e}, "
          f"xla {tol_x:.2e}")
    check(not beyond,
          f"accept set differs from the oracle beyond ties at {beyond}")

    # VMEM ceiling: the window's upper edge at the widest model
    del sim, noised, w1
    n_big, d_big = big
    xb = jax.random.normal(jax.random.PRNGKey(1), (n_big, d_big),
                           jnp.float32)
    big_p = np.asarray(krum_scores_pallas(xb, n_big // 2), np.float64)
    big_x = np.asarray(krum_scores(xb, n_big // 2), np.float64)
    check(bool(np.all(np.isfinite(big_p))), "n=4096 scores not finite")
    tol_big = rel(big_p, big_x)  # seen: 5.7e-7
    check(tol_big < 2e-5, f"n={n_big} d={d_big}: pallas vs xla {tol_big:.2e}")
    return {"contributors": s, "mosaic_in_round_hlo": True,
            "pallas_vs_oracle_max_rel": tol_p,
            "xla_vs_oracle_max_rel": tol_x,
            "pallas_vs_xla_max_rel": rel(got_p, got_x),
            "accept_differs_from_oracle": int(differ.size),
            "accept_differs_beyond_ties": len(beyond),
            "vmem_ceiling": {"n": n_big, "d": d_big, "ran": True,
                             "pallas_vs_xla_max_rel": tol_big}}


def phase_device_crypto():
    import numpy as np

    from biscotti_tpu.crypto import commitments as cm
    from biscotti_tpu.crypto import kernels
    from biscotti_tpu.ops import secretshare as ss

    k, total, rows, d = 10, 20, 10, 40
    q = np.arange(-d // 2, d - d // 2, dtype=np.int64) * 7
    c = ss.num_chunks(d, k)
    xs = [i - ss.SHARE_OFFSET for i in range(total)]
    comms, blinds = cm.vss_commit_chunks(q.reshape(c, k), b"smoke" * 6,
                                         b"ctx")
    br = cm.vss_blind_rows(blinds, xs)
    sh = np.asarray(ss.make_shares(q, k, total))

    def settle():
        acc = cm.VssIntakeBatch(rows, c, k)
        check(acc.add(0, comms, sh[:rows], br[:rows]), "intake refused")
        check(acc.fold() == [], "an honest grid was evicted")
        check(acc.verify(xs[:rows]), "an honest settle failed")
        return acc

    available = kernels.available()
    reason = kernels.availability_reason()
    kernels.reset_counters()
    if available:
        kernels.set_enabled(True)
        said = kernels.active()
        acc = settle()
        kernels.set_enabled(False)
        ran_on_device = (acc._acc_dev is not None
                         and kernels.device_calls().get("msm", 0) > 0)
    else:
        # armed-but-unavailable is a refusal at start-up, through the
        # entry point, carrying the compiler's message
        refused = ""
        try:
            run_hive(["-t", "4", "--iterations", "1", "--device-crypto",
                      "1", "-p", "9200"])
        except RuntimeError as e:
            refused = str(e)
        check(reason and reason in refused,
              f"hive.main --device-crypto 1 did not refuse with the "
              f"compiler's reason: {refused!r}")
        said = kernels.active()
        settle()
        ran_on_device = bool(kernels.device_calls())
    check(said == ran_on_device,
          f"kernels.active() said {said} but device ran: {ran_on_device}")
    return {"available": available, "reason": reason,
            "settled_on": "device" if ran_on_device else "cpu"}


def phase_multichip(nodes=100, model="mnist_cnn"):
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator, make_sharded_round_step
    from biscotti_tpu.runtime.hive import HiveStepper

    devices = jax.devices()
    n_dev = len(devices)
    mesh = jax.sharding.Mesh(np.array(devices), ("peers",))
    one = jax.sharding.Mesh(np.array(devices[:1]), ("peers",))
    cfg = BiscottiConfig(
        dataset="mnist", model_name=model, num_nodes=nodes, noising=True,
        verification=True, defense=Defense.KRUM, batch_size=10, epsilon=1.0,
        sample_percent=1.0, seed=0)
    check(nodes % n_dev == 0, f"{nodes} peers do not divide over {n_dev}")
    sim = Simulator(cfg)
    w0 = jnp.zeros((sim.num_params,), jnp.float32)

    # the sharded round step: all devices vs the same step on one
    step_n = make_sharded_round_step(sim, mesh)
    step_1 = make_sharded_round_step(sim, one)
    placed = {sh.device for sh in step_n.x.addressable_shards}
    check(len(placed) == n_dev, f"peer shards sit on {len(placed)} devices")
    # to the host: the two results live on different device sets
    wn, mask_n, _ = (np.asarray(a) for a in step_n(w0, 0))
    w1, mask_1, _ = (np.asarray(a) for a in step_1(w0, 0))
    check(bool(np.all(np.isfinite(wn))), "sharded w is not finite")
    check(int(mask_n.sum()) == nodes - nodes // 2, "sharded accept count")
    check(bool(np.all(mask_n == mask_1)), "accept sets differ by mesh")
    sim_diff = float(np.max(np.abs(wn - w1)))
    check(sim_diff <= 1e-5, f"sharded vs one-chip w differ by {sim_diff}")

    # the hive's batched plane: same peers, mesh vs one device
    ids = range(nodes)
    hs_n, hs_1 = HiveStepper(cfg, ids, mesh=mesh), HiveStepper(cfg, ids)
    check(hs_n.n_dev == n_dev, f"stepper used {hs_n.n_dev} devices")
    placed = {sh.device for sh in hs_n._x.addressable_shards}
    check(len(placed) == n_dev, f"hive shards sit on {len(placed)} devices")

    async def deltas(stepper):
        w = np.zeros(sim.num_params, np.float64)
        return np.stack([await stepper.step(i, w, 0) for i in ids])

    dn, d1 = asyncio.run(deltas(hs_n)), asyncio.run(deltas(hs_1))
    check(hs_n.batches == 1, "the mesh stepper did not batch the round")
    hive_diff = float(np.max(np.abs(dn - d1)))
    check(hive_diff <= 1e-5, f"hive deltas differ by {hive_diff} by mesh")

    # and through the entry point: main builds the mesh itself
    rc, s, minted = run_hive(
        ["-t", str(nodes), "-d", "mnist", "--model", model,
         "--iterations", "3", "-sa", "0", "-np", "1", "-vp", "1",
         "-p", "9400"])
    check(rc == 0 and s["chains_equal_local"], "mesh hive: chains differ")
    check(s["devices_used"] == n_dev,
          f"hive.main used {s['devices_used']} of {n_dev} devices")
    check(minted >= 2, f"mesh hive minted {minted} non-empty blocks")
    return {"devices": n_dev, "sharded_vs_one_chip_max_abs": sim_diff,
            "hive_mesh_vs_one_chip_max_abs": hive_diff,
            "hive_s_per_iter": s["s_per_iter"],
            "hive_devices_used": s["devices_used"],
            "hive_non_empty_blocks": minted}


# -------------------------------------------------------------------- main


def start():
    """x64 on, once, before anything touches JAX; no chip, no run."""
    import jax

    jax.config.update("jax_enable_x64", True)
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: jax.default_backend() is "
            f"{jax.default_backend()!r}, not 'tpu' — this script only "
            f"means something on the chip (CPU: pytest tests/)")


def run(phases):
    """Run the named phases in order; returns the summary object."""
    start()
    from biscotti_tpu.crypto import _native
    from biscotti_tpu.utils import jaxenv

    cache_dir = jaxenv.configure_compile_cache()
    device = jaxenv.device_info()
    check(_native.load_error() == "",
          f"native crypto library: {_native.load_error()}")
    meter = CompileMeter()
    table = {
        "device_round": lambda: phase_device_round(meter),
        "hive": lambda: phase_hive(meter, device),
        "pallas": phase_pallas,
        "device_crypto": phase_device_crypto,
        "multichip": phase_multichip,
    }
    results = {}
    for name in phases:
        note(f"{name} ...")
        mark = meter.mark()
        t0 = time.perf_counter()
        detail = table[name]()
        results[name] = {"wall_s": round(time.perf_counter() - t0, 2),
                         **meter.since(mark), **detail}
        note(f"{name} ok: {json.dumps(results[name])}")
    total = meter.since((0, 0, 0))
    return {
        **device,
        "cache_dir": cache_dir,
        "compile_s": total["compile_s"],
        "cache_hits": total["cache_hits"],
        "cache_misses": total["cache_misses"],
        "phases": results,
        "claim": None,
    }


def main():
    start()
    import jax

    phases = ["device_round", "hive", "pallas", "device_crypto"]
    if len(jax.devices()) > 1:
        phases.append("multichip")
    summary = run(phases)
    print(json.dumps(summary))
    # the driver's contract: this object, these keys, the last line
    print(json.dumps({"ok": True, "device": {
        "platform": summary["platform"], "kind": summary["device_kind"],
        "count": summary["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
