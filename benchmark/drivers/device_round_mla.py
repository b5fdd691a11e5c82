"""Driver `device_round_mla`: whole rounds on the device alone, of
DeepSeek-V2's share (latent attention, a group-limited router, a frozen
base held once beside the adapters it trains).

The program under test is `biscotti_tpu.parallel.sim.Simulator`, as in
`device_round_lm`, whose set-up, window and numbers this driver keeps: one
`round_step` a round, each ended by `block_until_ready`, closed loop; the
checked round from seeded NON-zero adapters; `w_next_leaf_gap`,
`logit_gap`, `router_flips_beyond_ties`, `accept_beyond_ties`,
`stake_mismatch`, `rounds_failed`, `compiles_in_window`, every round
failed where a token was dropped. What differs is the model's family: the
published keys the reference reads (`reference_spec`), the sizes the
program must have built (`check_sizes`), the reference itself
(`benchmark/reference/deepseek_v2.py`), the program's own routing
(`models/deepseek_v2.routing`), the controls, and what counts as a tie of
the router: a group-limited router has a second cut, between the last
group it keeps and the first it leaves (`router_flips`).
`device_round_lm`'s helpers that name no family are imported as they are.
"""

import contextlib
import time

import numpy as np

from benchmark.drivers.device_round import load_shards
from benchmark.drivers.device_round_lm import (LIMITS, _precision,  # noqa: F401
                                               limits_of)

CONTROLS = {  # name -> the reference's variant put in the program's place
    "bfloat16": {"store": "bfloat16"},    # adapters, deltas and sums
    "five_experts": {"fewer_experts": 1},  # five a token where six
    "no_groups": {"groups": False},       # the six largest of all 160
    "renormalised": {"renormalise": True},
    "no_scale": {"scale": 1.0},           # the 16 left out
    "no_shared_rope": {"shared_rope": False},
    "no_inner_norms": {"inner_norms": False},
    "no_mscale": {"mscale": False},       # m^2 left out of the scale
    "no_shared": {"shared": False},
}

PUBLISHED = ("hidden_size", "num_attention_heads", "q_lora_rank",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "num_hidden_layers", "first_k_dense_replace",
             "n_group", "topk_group", "num_experts_per_tok",
             "routed_scaling_factor", "norm_topk_prob", "rope_theta",
             "rope_scaling", "rms_norm_eps")


def reference_spec(config):
    """What `reference/deepseek_v2.py` needs of the configuration's file:
    the published keys at the layers held, and the adapters."""
    spec = {key: config[key] for key in PUBLISHED}
    spec["first_expert"] = config["model"]["held_first_expert"]
    spec["lora_rank"] = config["adapters"]["rank"]
    spec["lora_alpha"] = config["adapters"]["alpha"]
    return spec


def check_sizes(sim, config):
    """The program's model is the configuration's: d, the frozen count and
    every width the file states."""
    import jax

    from benchmark.reference import deepseek_v2 as ref

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
    heads = config["num_attention_heads"]
    found = {"held experts": tuple(last["experts"]["w_gate"].shape),
             "shared experts": tuple(last["shared"]["w_gate"].shape),
             "router outputs": last["router"].shape[1],
             "vocabulary rows": sim.frozen["embed"].shape[0],
             "query up": tuple(last["w_qb"].shape),
             "key/value down": tuple(last["w_kva"].shape),
             "key/value up": tuple(last["w_kvb"].shape)}
    stated = {"held experts": (config["n_routed_experts"],
                               config["hidden_size"],
                               config["moe_intermediate_size"]),
              "shared experts": (config["hidden_size"],
                                 config["n_shared_experts"]
                                 * config["moe_intermediate_size"]),
              "router outputs": config["published"]["n_routed_experts"],
              "vocabulary rows": config["vocab_size"],
              "query up": (config["q_lora_rank"], heads * (
                  config["qk_nope_head_dim"] + config["qk_rope_head_dim"])),
              "key/value down": (config["hidden_size"],
                                 config["kv_lora_rank"]
                                 + config["qk_rope_head_dim"]),
              "key/value up": (config["kv_lora_rank"], heads * (
                  config["qk_nope_head_dim"] + config["v_head_dim"]))}
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

    from biscotti_tpu.models import deepseek_v2

    model, cfg = sim.model, sim.model.info["config"]

    @jax.jit
    def view(w, x, frozen):
        experts, _ = deepseek_v2.routing(cfg, model.unravel(w), x, frozen)
        return model.apply_flat(w, x, frozen), experts

    logits, experts = view(jnp.asarray(w, jnp.float32), jnp.asarray(x_val),
                           sim.frozen)
    return np.asarray(logits, np.float64), np.asarray(experts)


def reference_view(spec, frozen, w, x_val, dtype, variant=None):
    """The reference's: logits, and per sparse layer the chosen experts
    [N, k] and all the probabilities [N, E_all]."""
    import jax.numpy as jnp

    from benchmark.reference import deepseek_v2 as ref

    variant = {k: v for k, v in (variant or {}).items() if k != "store"}
    _, run = ref.compiled(spec, dtype, variant)
    logits, picks = run(frozen, jnp.asarray(w, dtype), jnp.asarray(x_val))
    return (np.asarray(logits, np.float64),
            [np.asarray(e) for e, _ in picks],
            [np.asarray(p, np.float64) for _, p in picks])


def _kept_groups(probs, groups, kept, band):
    """The sets of `kept` groups a group-limited router may keep for one
    token's probabilities `probs` [E_all] when two group maxima within
    `band` (relative) of the cut, the last kept group's maximum, count as
    equal: the reference's own set first."""
    import itertools

    best = probs.reshape(groups, -1).max(axis=1)
    order = np.argsort(-best, kind="stable")
    cut = best[order[kept - 1]]
    tied = [g for g in order if abs(best[g] - cut) <= band * cut]
    sure = [g for g in order[:kept] if g not in tied]
    own = tuple(sorted(order[:kept]))
    found = [own] + [
        tuple(sorted(sure + list(some)))
        for some in itertools.combinations(tied, kept - len(sure))]
    return list(dict.fromkeys(found))


def router_flips(chosen, ref_experts, ref_probs, band, groups, kept):
    """(token-slot choices that differ from the reference's OUTSIDE the
    router's ties, the worst relative distance from a cut among those
    judged, which tokens differ anywhere: bool[N]).

    `device_round_lm.router_flips` with the group-limited router's second
    cut. A token is judged at the FIRST sparse layer where its set of
    experts differs from the reference's (from there on its hidden state is
    another). There the reference's probabilities are held against every
    set of groups the router may keep when two group maxima within `band`
    (relative) of the last kept one's count as equal (`_kept_groups`: the
    reference's own set where no group ties), each with its own `k`
    largest; a differing choice whose probability lies within `band` of
    that set's k-th is rounding. The token counts the fewest differences
    beyond ties that any such set leaves: none where the program kept
    another of two near-equal groups and then chose as the reference
    would have. A slot the program leaves empty differs, whatever its
    probability; so does an expert of a group that no admissible set
    keeps."""
    beyond, worst = 0, 0.0
    settled = np.zeros(len(ref_experts[0]), bool)  # differed at a layer before
    for got, want, probs in zip(chosen, ref_experts, ref_probs):
        k, size = want.shape[1], probs.shape[1] // groups
        same = np.array([set(a) == set(b) for a, b in zip(got, want)])
        beyond += max(0, k - got.shape[1]) * int(np.sum(~settled & same))
        for token in np.nonzero(~settled & ~same)[0]:
            p, mine = probs[token], set(got[token].tolist())
            fewest = None
            for kept_set in _kept_groups(p, groups, kept, band):
                eligible = np.where(np.isin(np.arange(len(p)) // size,
                                            kept_set), p, 0.0)
                theirs = set(np.argsort(-eligible, kind="stable")[:k]
                             .tolist())
                kth = min(eligible[e] for e in theirs)
                off = [float(abs(p[e] - kth) / kth) for e in mine ^ theirs]
                count = (sum(o > band for o in off)
                         + max(0, k - len(mine)))
                if fewest is None or (count, max(off, default=0.0)) < fewest:
                    fewest = (count, max(off, default=0.0))
            beyond += fewest[0]
            worst = max(worst, fewest[1])
        settled |= ~same
    return int(beyond), float(worst), settled


def leaf_gaps(spec, got, ref):
    """|got - ref| (L2) of every adapter leaf over the larger of that
    leaf's reference norm and the median leaf's."""
    from benchmark.reference import deepseek_v2 as rd

    ref_leaves = rd.leaves(spec, np.asarray(ref, np.float64))
    got_leaves = rd.leaves(spec, np.asarray(got, np.float64))
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
    flips, worst_flip, differ_at = router_flips(
        got["experts"], experts, probs, float(mix["router_band"]),
        spec["n_group"], spec["topk_group"])
    # the logits of the positions whose routing agreed at every layer
    agreed = ~differ_at.reshape(logits.shape[:-1])
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
            "worst_flip_rel": worst_flip,
            "tokens_differ": int(differ_at.sum()),
            "logit_gap_where_routing_agrees": float(
                np.linalg.norm((got["logits"] - logits)[agreed])
                / np.linalg.norm(logits[agreed])) if agreed.any() else 0.0,
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

    from benchmark.reference import deepseek_v2 as ref

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
