"""Driver `device_round`: whole rounds on the device alone.

The program under test is `biscotti_tpu.parallel.sim.Simulator`, the
object a user of `python -m biscotti_tpu.parallel.sim` drives: one
`round_step` a round (every sampled peer's SGD step, DP noise, Krum, the
sum of the accepted deltas, the stake ledger, the test error, as one XLA
program), each ended by `block_until_ready`. Closed loop: a round starts
when the last one has ended.

Set-up builds ONE Simulator, drives it through its first `warm_rounds`
rounds with the window's own call, each checked round from weights drawn
from the seed, keeps what those rounds were given and returned for the
comparison, and hands the same object and state to the window.

The peers' shards are the program's own (`data.datasets.load_shard`, cached
by name). `Simulator.__init__` makes them one after another; at thousands
of peers that is most of a minute, so set-up first asks the same function
for every shard from a few threads and the Simulator then finds them made.
"""

import contextlib
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def load_shards(cfg):
    """Every peer's shard through the program's loader, in bulk."""
    from biscotti_tpu.data import datasets as ds
    from biscotti_tpu.parallel.sim import _poisoned_ids

    bad = _poisoned_ids(cfg.num_nodes, cfg.poison_fraction)
    names = [ds.shard_name(cfg.dataset, i, i in bad)
             for i in range(cfg.num_nodes)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        list(pool.map(lambda name: ds.load_shard(cfg.dataset, name), names))


def run(cell, fields, seconds, trace_dir, meter, t0):
    import jax
    import jax.numpy as jnp

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator

    from benchmark import trace as trace_reduction
    from benchmark.reference import models as rm

    mix = cell["mix"]
    seed = fields["seed"]
    fields = dict(fields, defense=Defense[fields["defense"]])
    cfg = BiscottiConfig(**fields)
    load_shards(cfg)
    sim = Simulator(cfg)
    model = cell["config"]["biscotti"]["model_name"]
    want_d = cell["config"]["model"]["num_params"]
    if sim.num_params != want_d or rm.num_params(model) != want_d:
        raise RuntimeError(f"d = {sim.num_params}, the configuration "
                           f"states {want_d}")

    _, stake = sim.init_state()
    seen = []  # what the first rounds were given and returned
    it = 0
    for it in range(int(mix["warm_rounds"])):
        if it < max(1, int(mix["checked_rounds"])):
            # every checked round starts from weights drawn from the seed:
            # a round adds the SUM of the accepted steps, so one round on,
            # the weights are large, the softmax saturates, and a round's
            # update is a few borderline rows that float32 and float64
            # class differently (the gap read 1.0 in 3 seeds of 9)
            w = jnp.asarray(rm.init_weights(model, seed + it))
        w_in, stake_in = np.asarray(w), np.asarray(stake)  # donated below
        w, stake, mask, err = sim.round_step(w, stake, it)
        jax.block_until_ready(w)
        if it < int(mix["checked_rounds"]):
            seen.append({"it": it, "w_in": w_in, "stake_in": stake_in,
                         "w_next": np.asarray(w),
                         "stake_next": np.asarray(stake),
                         "mask": np.asarray(mask), "err": float(err)})
    it += 1

    tracing = trace_dir is not None
    if tracing:
        seconds = min(seconds, float(mix["trace_seconds"]))
        trace_reduction.start(trace_dir)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda name: contextlib.nullcontext()  # noqa: E731

    setup_s = time.time() - t0
    durs, masks = [], []
    epoch0 = time.time()
    start = time.perf_counter()
    end = start + seconds
    t1 = start
    while True:
        with span("bench:round_step dispatch"):
            w, stake, mask, err = sim.round_step(w, stake, it)
        with span("bench:block_until_ready"):
            jax.block_until_ready(w)
        t2 = time.perf_counter()
        durs.append(t2 - t1)
        masks.append(mask)
        it += 1
        if t2 >= end:
            break
        t1 = t2
    elapsed = t2 - start
    epoch1 = time.time()
    if tracing:
        jax.profiler.stop_trace()

    # a round fails when its accept count is not Krum's s - s//2 (all s
    # with verification off); weights that are not finite fail them all
    s = cfg.num_samples
    want = s - s // 2 if cfg.verification else s
    accepted = np.asarray([int(np.asarray(m).sum()) for m in masks])
    finite = bool(np.all(np.isfinite(np.asarray(w))))
    failed = int(np.sum(accepted != want)) if finite else len(durs)
    return {
        "cell": cell, "cfg": cfg, "sim": sim, "seed": seed, "seen": seen,
        "round_s": durs, "attempted": len(durs), "failed": failed,
        "compiles_in_window": meter.between(epoch0, epoch1),
        "end_to_end": {
            # all the window's time over all its rounds
            "device_round_ms": 1e3 * elapsed / len(durs),
            "device_round_ms.p95": 1e3 * float(np.quantile(durs, 0.95)),
            "setup_s": setup_s,
        },
    }


# Exact limits are the driver's. The measured ones (`w_next_leaf_gap`,
# `err_gap`) depend on the model, the scale and the mix, so they stand in
# the configuration's file under `limits[<mix>]` (or in a later mix's file
# under `limits[<config>]`); PERF.md section 2 gives the readings each was
# set from. A number without a limit comes out as not correct.
LIMITS = {"rounds_failed": 0, "compiles_in_window": 0,
          "accept_beyond_ties": 0, "stake_mismatch": 0,
          "w_next_leaf_gap": None, "err_gap": None}
TIE_REL = 1e-4  # Krum score error a tie may hide behind (PERF.md section 2)


def limits_of(cell):
    config, mix = cell["config"], cell["mix"]
    return dict(LIMITS, **config.get("limits", {}).get(mix["name"], {}),
                **mix.get("limits", {}).get(config["name"], {}))


def round_spec(cfg, cell):
    """What the reference needs to know of the round, and the inputs."""
    from biscotti_tpu.data import datasets as ds

    dataset = cfg.dataset
    spec = {"model": cell["config"]["biscotti"]["model_name"],
            "n": cfg.num_nodes, "s": cfg.num_samples,
            "rows": len(ds.load_shard(dataset, f"{dataset}0")["x_train"]),
            "batch": cfg.batch_size, "clip": cfg.grad_clip,
            "epsilon": cfg.epsilon, "delta": cfg.delta,
            "noising": cfg.noising, "verification": cfg.verification,
            "stake_unit": cfg.stake_unit}

    def shard_rows(peer, idx):  # inputs only: the peers' own data
        shard = ds.load_shard(dataset, f"{dataset}{peer}")
        return shard["x_train"][idx], shard["y_train"][idx]

    test = ds.load_shard(dataset, f"{dataset}_test")
    return spec, shard_rows, test["x_test"], test["y_test"]


def compare(spec, seed, got, shard_rows, x_val, y_val):
    """One round's returns (`got`: it, w_in, stake_in, w_next, stake_next,
    mask, err) against the float64 reference of the same round."""
    from benchmark.reference import krum as rkrum
    from benchmark.reference import round as rround

    ref = rround.reference_round(
        spec, seed, got["it"], got["w_in"], got["stake_in"], shard_rows,
        x_val, y_val, accept_from=got["mask"])
    beyond = rkrum.beyond_ties(ref["scores"], ref["accept"], got["mask"],
                               TIE_REL) if spec["verification"] else []
    update = (np.asarray(got["w_next"], np.float64)
              - np.asarray(got["w_in"], np.float64))
    differ = np.nonzero(np.asarray(got["mask"], bool) != ref["accept"])[0]
    order = np.sort(ref["scores"])
    keep = int(ref["accept"].sum())
    cut = 0.5 * (order[keep - 1] + order[min(keep, len(order) - 1)])
    return {
        "_detail": {
            "leaves": rround.leaf_gaps(spec["model"], update, ref["agg"]),
            "err": got["err"], "err_ref": ref["err"],
            "accept_differs": int(differ.size),
            # how far from the cut the program's disagreements sit
            "worst_tie_rel": max((abs(ref["scores"][i] - cut) / abs(cut)
                                  for i in differ), default=0.0)
            if cut else 0.0},
        "accept_beyond_ties": len(beyond),
        "stake_mismatch": int(np.sum(ref["stake_next"]
                                     != got["stake_next"])),
        "w_next_leaf_gap": rround.leaf_gap(spec["model"], update,
                                           ref["agg"]),
        "err_gap": abs(ref["err"] - got["err"]),
    }


def control_round(spec, seed, got, shard_rows, x_val, y_val, precision):
    """The control: the reference in a lower precision, put in the
    program's place for the same round (same weights in, same draws)."""
    from benchmark.reference import round as rround

    low = rround.reference_round(
        spec, seed, got["it"], got["w_in"], got["stake_in"], shard_rows,
        x_val, y_val, precision=precision)
    return dict(got, w_next=low["w_next"], stake_next=low["stake_next"],
                mask=low["accept"], err=low["err"])


def check(record, control=None, limits=None):
    """Hold what the first rounds of the timed object returned to the
    float64 reference: [(name, value, limit, ok)], the worst round of
    each number. `control` names a precision: the reference computed in
    it then stands in the program's place (tests and limit-setting)."""
    t0 = time.perf_counter()
    limits = dict(limits_of(record["cell"]), **(limits or {}))
    record.pop("sim", None)  # the program's state is freed first
    spec, shard_rows, x_val, y_val = round_spec(record["cfg"],
                                                record["cell"])
    worst = {"rounds_failed": record["failed"],
             "compiles_in_window": record["compiles_in_window"]}
    for got in record["seen"]:
        if control:
            got = control_round(spec, record["seed"], got, shard_rows,
                                x_val, y_val, control)
        found = compare(spec, record["seed"], got, shard_rows, x_val, y_val)
        record.setdefault("detail", []).append(found.pop("_detail"))
        for name, value in found.items():
            worst[name] = max(worst.get(name, 0), value)
    record["check_s"] = time.perf_counter() - t0
    return [(name, value, limits[name],
             limits[name] is not None and value <= limits[name])
            for name, value in worst.items()]
