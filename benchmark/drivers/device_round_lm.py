"""Driver `device_round_lm`: whole rounds on the device alone, of a model
that holds a frozen base beside what it trains.

The program under test is `biscotti_tpu.parallel.sim.Simulator`, as in
`device_round`: one `round_step` a round, each ended by
`block_until_ready`, closed loop. What differs is the model (a
sparse-expert decoder whose adapters are the committed vector, token
shards, a frozen tree the program holds once) and so the comparison:
`benchmark/reference/laguna.py` stands in `reference/models.py`'s place,
takes the program's OWN frozen arrays and shards as inputs, and is
computed on the device in float32 at the highest matmul precision (float64
on the CPU, in the tests), a peer at a time.

Set-up builds ONE Simulator and drives it through `warm_rounds` rounds with
the window's own call. The checked round starts from seeded NON-zero
adapters (`model.flat_init`: a round's own start, zeros, would leave the
adapters out of the forward). What the check compares, beyond
`device_round`'s numbers: the logits of the held-out windows under those
adapters (`logit_gap`: with random weights the error is 1 - 1/V on both
sides and says nothing) and the router's choices
(`router_flips_beyond_ties`).
"""

import contextlib
import time

import numpy as np

from benchmark.drivers.device_round import load_shards

CONTROLS = {  # name -> the reference's variant put in the program's place
    "bfloat16": {"store": "bfloat16"},   # adapters, deltas and sums
    "nine_experts": {"fewer_experts": 1},  # nine a token where ten
    "no_shared": {"shared": False},
    "no_window": {"window": False},
    "no_gate": {"gate": False},
    "no_scale": {"scale": 1.0},
}


def reference_spec(config):
    """What `reference/laguna.py` needs of the configuration's file: the
    published keys cut to the layers held, and the adapters."""
    layers = config["num_hidden_layers"]
    spec = {key: config[key] for key in (
        "hidden_size", "head_dim", "num_key_value_heads", "mlp_only_layers",
        "sliding_window", "num_experts_per_tok", "moe_routed_scaling_factor",
        "rope_parameters", "rms_norm_eps")}
    spec["num_attention_heads_per_layer"] = \
        config["num_attention_heads_per_layer"][:layers]
    spec["layer_types"] = config["layer_types"][:layers]
    spec["first_expert"] = config["model"]["held_first_expert"]
    spec["lora_rank"] = config["adapters"]["rank"]
    spec["lora_alpha"] = config["adapters"]["alpha"]
    return spec


def check_sizes(sim, config):
    """The program's model is the configuration's: d, the frozen count and
    every width the file states."""
    import jax

    from benchmark.reference import laguna as ref

    want = config["model"]
    frozen = sum(a.size for a in jax.tree.leaves(sim.frozen))
    d_ref = ref.num_params(reference_spec(config))
    if not (sim.num_params == d_ref == want["num_params"]):
        raise RuntimeError(f"d = {sim.num_params} (the reference's layout "
                           f"{d_ref}), the configuration states "
                           f"{want['num_params']}")
    if frozen != want["frozen_params"]:
        raise RuntimeError(f"{frozen} frozen parameters, the configuration "
                           f"states {want['frozen_params']}")
    last = sim.frozen["layers"][-1]
    found = {"held experts": tuple(last["experts"]["w_gate"].shape),
             "router outputs": last["router"].shape[1],
             "vocabulary rows": sim.frozen["embed"].shape[0]}
    stated = {"held experts": (config["num_experts"], config["hidden_size"],
                               config["moe_intermediate_size"]),
              "router outputs": config["published"]["num_experts"],
              "vocabulary rows": config["vocab_size"]}
    if found != stated:
        raise RuntimeError(f"the program holds {found}, the configuration "
                           f"states {stated}")


def run(cell, fields, seconds, trace_dir, meter, t0):
    import jax

    from biscotti_tpu.config import BiscottiConfig, Defense
    from biscotti_tpu.parallel.sim import Simulator

    from benchmark import trace as trace_reduction

    mix = cell["mix"]
    seed = fields["seed"]
    cfg = BiscottiConfig(**dict(fields, defense=Defense[fields["defense"]]))
    load_shards(cfg)
    sim = Simulator(cfg)
    check_sizes(sim, cell["config"])

    _, stake = sim.init_state()
    seen = []  # what the checked rounds were given and returned
    it = 0
    for it in range(int(mix["warm_rounds"])):
        if it < max(1, int(mix["checked_rounds"])):
            w = sim.model.flat_init(jax.random.PRNGKey(seed + it))
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
    durs, masks, counted = [], [], []
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
        counted.append(getattr(sim, "last_counts", {}))
        it += 1
        if t2 >= end:
            break
        t1 = t2
    elapsed = t2 - start
    epoch1 = time.time()
    if tracing:
        jax.profiler.stop_trace()

    s = cfg.num_samples
    want = s - s // 2 if cfg.verification else s
    accepted = np.asarray([int(np.asarray(m).sum()) for m in masks])
    finite = bool(np.all(np.isfinite(np.asarray(w))))
    failed = int(np.sum(accepted != want)) if finite else len(durs)
    # the program's own routing counts, a round (read back after the window)
    stats = [sim.dispatch_stats(c) for c in counted]
    moe = {name: [row[name] for row in stats]
           for name in (stats[0] if stats else {})}
    dropped = sum(moe.get("tokens_dropped", []))
    return {
        "cell": cell, "cfg": cfg, "sim": sim, "seed": seed, "seen": seen,
        "round_s": durs, "attempted": len(durs),
        "failed": failed if not dropped else len(durs),
        "compiles_in_window": meter.between(epoch0, epoch1),
        "moe": moe, "peer_block": sim.peer_block,
        "end_to_end": {
            "device_round_ms": 1e3 * elapsed / len(durs),
            "device_round_ms.p95": 1e3 * float(np.quantile(durs, 0.95)),
            "setup_s": setup_s,
        },
    }


# Exact limits are the driver's; the measured ones stand in the
# configuration's file under `limits[<mix>]`, each with the readings it was
# set from in PERF.md section 2. A number without a limit comes out as not
# correct.
LIMITS = {"rounds_failed": 0, "compiles_in_window": 0,
          "accept_beyond_ties": 0, "stake_mismatch": 0,
          "router_flips_beyond_ties": 0, "w_next_leaf_gap": None,
          "err_gap": None, "logit_gap": None}


def limits_of(cell):
    config, mix = cell["config"], cell["mix"]
    return dict(LIMITS, **config.get("limits", {}).get(mix["name"], {}),
                **mix.get("limits", {}).get(config["name"], {}))


def _precision(platform):
    """(dtype, context) of the reference: float32 at the highest matmul
    precision on the chip; float64 where the backend has it (the CPU)."""
    import jax
    import jax.numpy as jnp

    if platform == "cpu":
        return jnp.float64, contextlib.nullcontext()
    return jnp.float32, jax.default_matmul_precision("highest")


def _round_inputs(record):
    from biscotti_tpu.data import datasets as ds

    cfg, config = record["cfg"], record["cell"]["config"]
    dataset = cfg.dataset
    rnd = {"n": cfg.num_nodes, "s": cfg.num_samples,
           "rows": len(ds.load_shard(dataset, f"{dataset}0")["x_train"]),
           "batch": cfg.batch_size, "clip": cfg.grad_clip,
           "eta": cfg.learning_rate, "epsilon": cfg.epsilon,
           "delta": cfg.delta, "noising": cfg.noising,
           "verification": cfg.verification, "stake_unit": cfg.stake_unit}

    def shard_rows(peer, idx):  # inputs only: the peers' own data
        shard = ds.load_shard(dataset, f"{dataset}{peer}")
        return shard["x_train"][idx], shard["y_train"][idx]

    test = ds.load_shard(dataset, f"{dataset}_test")
    return (reference_spec(config), rnd, shard_rows, test["x_test"],
            test["y_test"])


def program_view(sim, w, x_val):
    """What the PROGRAM makes of the held-out windows under adapters `w`:
    its logits [b, T, V] and its router's choices [L, b*T, k], through the
    model the round itself runs."""
    import jax
    import jax.numpy as jnp

    from biscotti_tpu.models import laguna

    model, cfg = sim.model, sim.model.info["config"]

    @jax.jit
    def view(w, x, frozen):
        experts, _ = laguna.routing(cfg, model.unravel(w), x, frozen)
        return model.apply_flat(w, x, frozen), experts

    logits, experts = view(jnp.asarray(w, jnp.float32), jnp.asarray(x_val),
                           sim.frozen)
    return np.asarray(logits, np.float64), np.asarray(experts)


def reference_view(spec, frozen, w, x_val, dtype, variant=None):
    """The reference's: logits, and per sparse layer the chosen experts
    [N, k] and all the probabilities [N, E_all]."""
    import jax.numpy as jnp

    from benchmark.reference import laguna as ref

    variant = {k: v for k, v in (variant or {}).items() if k != "store"}
    _, run = ref.compiled(spec, dtype, variant)
    logits, picks = run(frozen, jnp.asarray(w, dtype), jnp.asarray(x_val))
    return (np.asarray(logits, np.float64),
            [np.asarray(e) for e, _ in picks],
            [np.asarray(p, np.float64) for _, p in picks])


def router_flips(chosen, ref_experts, ref_probs, band):
    """(token-slot choices that differ from the reference's OUTSIDE a band
    of `band` (relative) around the reference's k-th probability, the worst
    relative distance from it among those judged, tokens that differ
    anywhere).

    A token is judged at the FIRST sparse layer where its set of experts
    differs from the reference's: from there on its hidden state is another
    (one expert of ten swapped is a tenth of the routed sum), and what its
    later routers choose follows from that, not from a fault of theirs. A
    differing choice inside the band is rounding: the two sides rank two
    near-equal probabilities differently. A slot the program leaves empty
    differs, whatever its probability."""
    beyond, worst = 0, 0.0
    settled = np.zeros(len(ref_experts[0]), bool)  # differed at a layer before
    for got, want, probs in zip(chosen, ref_experts, ref_probs):
        cut = np.take_along_axis(probs, want, 1).min(axis=1)  # the k-th
        fresh = ~settled
        beyond += max(0, want.shape[1] - got.shape[1]) * int(fresh.sum())
        differs = np.zeros(len(want), bool)
        for mine, theirs in ((got, want), (want, got)):
            extra = ~(mine[:, :, None] == theirs[:, None, :]).any(axis=2)
            off = np.abs(np.take_along_axis(probs, mine, 1) - cut[:, None]) \
                / cut[:, None]
            judged = extra & fresh[:, None]
            beyond += int(np.sum(judged & (off > band)))
            worst = max(worst, float(np.max(np.where(judged, off, 0.0))))
            differs |= extra.any(axis=1)
        settled |= differs
    return beyond, worst, int(settled.sum())


def leaf_gaps(spec, got, ref):
    """|got - ref| (L2) of every adapter leaf over the larger of that
    leaf's reference norm and the median leaf's."""
    from benchmark.reference import laguna as rl

    ref_leaves = rl.leaves(spec, np.asarray(ref, np.float64))
    got_leaves = rl.leaves(spec, np.asarray(got, np.float64))
    norms = [float(np.linalg.norm(r)) for _, r in ref_leaves]
    floor = float(np.median(norms))
    return {name: float(np.linalg.norm(g - r)) / max(nr, floor, 1e-300)
            for (name, g), (_, r), nr in zip(got_leaves, ref_leaves, norms)}


def compare(spec, mix, got, ref, ref_view):
    """One round's returns against the reference's of the same round."""
    from benchmark.reference import krum as rkrum

    beyond = rkrum.beyond_ties(ref["scores"], ref["accept"], got["mask"],
                               float(mix["tie_rel"])) \
        if ref["scores"].any() else []
    update = (np.asarray(got["w_next"], np.float64)
              - np.asarray(got["w_in"], np.float64))
    gaps = leaf_gaps(spec, update, ref["agg"])
    logits, experts, probs = ref_view
    flips, worst_flip, tokens_differ = router_flips(
        got["experts"], experts, probs, float(mix["router_band"]))
    worst = sorted(gaps, key=gaps.get)[-3:]
    differ = np.nonzero(np.asarray(got["mask"], bool) != ref["accept"])[0]
    order = np.sort(ref["scores"])
    keep = int(ref["accept"].sum())
    cut = 0.5 * (order[keep - 1] + order[min(keep, len(order) - 1)])
    return {
        "_detail": {
            "worst_leaves": {name: gaps[name] for name in worst},
            "err": got["err"], "err_ref": ref["err"],
            "accept_differs": int(differ.size),
            # how far from the cut the program's disagreements sit
            "worst_tie_rel": max((abs(ref["scores"][i] - cut) / abs(cut)
                                  for i in differ), default=0.0)
            if cut else 0.0,
            "worst_flip_rel": worst_flip, "tokens_differ": tokens_differ,
            "delta_norms": [float(np.linalg.norm(row))
                            for row in ref["deltas"][:4]]},
        "accept_beyond_ties": len(beyond),
        "stake_mismatch": int(np.sum(ref["stake_next"]
                                     != got["stake_next"])),
        "router_flips_beyond_ties": flips,
        "w_next_leaf_gap": max(gaps.values()),
        "err_gap": abs(ref["err"] - got["err"]),
        "logit_gap": float(np.linalg.norm(got["logits"] - logits)
                           / np.linalg.norm(logits)),
    }


def check(record, control=None, limits=None):
    """Hold what the checked rounds of the timed object returned to the
    reference: [(name, value, limit, ok)]. `control` names one of
    CONTROLS: the reference with that departure then stands in the
    program's place (tests and limit-setting)."""
    import jax

    from benchmark.reference import laguna as ref

    t0 = time.perf_counter()
    limits = dict(limits_of(record["cell"]), **(limits or {}))
    mix = record["cell"]["mix"]
    sim = record["sim"]
    spec, rnd, shard_rows, x_val, y_val = _round_inputs(record)
    dtype, precision = _precision(jax.devices()[0].platform)
    worst = {"rounds_failed": record["failed"],
             "compiles_in_window": record["compiles_in_window"]}
    for got in record["seen"]:  # the program's side, at its own precision
        if "logits" not in got:
            got["logits"], got["experts"] = program_view(sim, got["w_in"],
                                                         x_val)
    with precision:
        for got in record["seen"]:
            if "_ref" not in got:  # once a record, whatever stands in
                got["_ref"] = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, accept_from=got["mask"])
                got["_ref_view"] = reference_view(
                    spec, sim.frozen, got["w_in"], x_val, dtype)
            truth, truth_view = got["_ref"], got["_ref_view"]
            if control:
                variant = CONTROLS[control]
                low = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, variant=variant)
                logits, experts, _ = reference_view(
                    spec, sim.frozen,
                    ref.bf16(got["w_in"]) if "store" in variant
                    else got["w_in"], x_val, dtype, variant)
                got = dict(got, w_next=low["w_next"], mask=low["accept"],
                           stake_next=low["stake_next"], err=low["err"],
                           logits=logits, experts=experts)
                # the oracle aggregates and pays with the set it is handed
                stake = np.array(got["stake_in"], np.int64)
                np.add.at(stake, truth["sampled"], np.where(
                    low["accept"], rnd["stake_unit"], -rnd["stake_unit"]))
                truth = dict(truth, stake_next=stake, agg=truth["deltas"][
                    low["accept"]].sum(axis=0))
            found = compare(spec, mix, got, truth, truth_view)
            record.setdefault("detail", []).append(found.pop("_detail"))
            for name, value in found.items():
                worst[name] = max(worst.get(name, 0), value)
    record["check_s"] = time.perf_counter() - t0
    return [(name, value, limits[name],
             limits[name] is not None and value <= limits[name])
            for name, value in worst.items()]
