"""Driver `device_round_swa`: whole rounds on the device alone, of
MiMo-V2.5's share (sliding-window attention with a learned sink beside full
attention 5 : 1, grouped queries at heads of 192 | 128, a sigmoid router
with a choice bias over 256 experts, a frozen base held once beside the
adapters it trains), on windows of 2,048 tokens.

The program under test is `biscotti_tpu.parallel.sim.Simulator`, as in
`device_round_lm`, whose set-up, window and numbers this driver keeps: one
`round_step` a round, each ended by `block_until_ready`, closed loop; the
checked round from seeded NON-zero adapters; `w_next_leaf_gap`,
`logit_gap`, `router_flips_beyond_ties` (a plain top-k of s + b: ONE cut,
so `device_round_lm.router_flips` as it is, on what the experts were
chosen BY), `accept_beyond_ties`, `stake_mismatch`, `rounds_failed`,
`compiles_in_window`, `err_gap`, every round failed where a token was
dropped. What differs is the model's family: the published keys the
reference reads (`reference_spec`), the sizes the program must have built
(`check_sizes`), the reference itself (`benchmark/reference/mimo_v2.py`:
the scores a dense [T, T] matrix with the sink as one more column), the
program's own routing (`models/mimo_v2.routing`) and the controls. As in
`device_round_ssm` and `device_round_gdn`, a control that departs in the
forward is judged at its logits first and steps no gradient where they
alone fail. `device_round_lm`'s helpers that name no family are imported
as they are. The checked round's `detail` also says what the program
itself reports of what is new: `sink_mass`, the mean probability a query
of the held-out windows gives its head's sink, and `peer_block`, the peers
the round steps together (no accepted metric lists this cell for it).
"""

import contextlib
import time

import numpy as np

from benchmark.drivers.device_round import load_shards
from benchmark.drivers.device_round_gdn import logit_gap
from benchmark.drivers.device_round_lm import (LIMITS, _precision,  # noqa: F401
                                               limits_of, router_flips)

CONTROLS = {  # name -> the reference's variant put in the program's place
    "bfloat16": {"store": "bfloat16"},    # adapters, deltas and sums
    "no_sink": {"sink": False},
    "sink_on_full": {"sink_on_full": True},
    "no_window": {"window": False},       # every layer causal
    "window_256": {"window": 256},
    "rotary_full": {"rotary": "full"},    # all 192 turned
    "one_theta": {"theta": "one"},        # rope_theta in window layers too
    "no_value_scale": {"value_scale": False},
    "kv_heads_swapped": {"kv_swapped": True},  # the other kind's grouping
    "softmax_router": {"router": "softmax"},
    "no_choice_bias": {"choice_bias": False},
    "no_renormalise": {"renormalise": False},
}

PUBLISHED = ("hidden_size", "num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "swa_num_key_value_heads", "head_dim",
             "v_head_dim", "partial_rotary_factor", "rope_theta",
             "swa_rope_theta", "sliding_window", "attention_value_scale",
             "hybrid_layer_pattern", "moe_layer_freq",
             "num_experts_per_tok", "norm_topk_prob", "layernorm_epsilon")


def reference_spec(config):
    """What `reference/mimo_v2.py` needs of the configuration's file: the
    published keys, the two lists cut to the layers held, and the
    adapters."""
    spec = {key: config[key] for key in PUBLISHED}
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        spec[key] = list(config[key][:config["num_hidden_layers"]])
    spec["first_expert"] = config["model"]["held_first_expert"]
    spec["lora_rank"] = config["adapters"]["rank"]
    spec["lora_alpha"] = config["adapters"]["alpha"]
    return spec


def check_sizes(sim, config):
    """The program's model is the configuration's: d, the frozen count and
    every width the file states."""
    import jax

    from benchmark.reference import mimo_v2 as ref

    want = config["model"]
    frozen = sum(a.size for a in jax.tree.leaves(sim.frozen))
    spec = reference_spec(config)
    d_ref = ref.num_params(spec)
    if not (sim.num_params == d_ref == want["num_params"]):
        raise RuntimeError(f"d = {sim.num_params} (the reference's layout "
                           f"{d_ref}), the configuration states "
                           f"{want['num_params']}")
    if frozen != want["frozen_params"]:
        raise RuntimeError(f"{frozen} frozen parameters, the configuration "
                           f"states {want['frozen_params']}")
    layers = sim.frozen["layers"]
    kinds = ref.kinds(spec)
    full = next(l for l, (kind, _) in zip(layers, kinds) if kind == "full")
    window = next(l for l, (kind, _) in zip(layers, kinds)
                  if kind == "window")
    sparse = next(l for l, (_, sparse) in zip(layers, kinds) if sparse)
    dense = next(l for l, (_, sparse) in zip(layers, kinds) if not sparse)
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    cfg = sim.model.info["config"]
    found = {"layers": [("window" if "sink" in layer else "full",
                         "experts" in layer) for layer in layers],
             "untied": "head" in sim.frozen,
             "vocabulary rows": sim.frozen["embed"].shape[0],
             "held experts": tuple(sparse["experts"]["w_gate"].shape),
             "router outputs": sparse["router"].shape[1],
             "choice biases": tuple(sparse["router_bias"].shape),
             "shared expert": "shared" in sparse,
             "dense": tuple(dense["dense"]["w_gate"].shape),
             "qkv full": tuple(full["w_qkv"].shape),
             "qkv window": tuple(window["w_qkv"].shape),
             "o": tuple(window["wo"].shape),
             "sinks": tuple(window["sink"].shape),
             "window": cfg.window, "rotated": cfg.rotary,
             "theta": tuple(cfg.rope_theta), "value scale": cfg.value_scale,
             "experts a token": cfg.top_k,
             "tokens": int(sim.x.shape[-1])}
    stated = {"layers": kinds,
              "untied": not config["tie_word_embeddings"],
              "vocabulary rows": config["vocab_size"],
              "held experts": (config["n_routed_experts"], hidden,
                               config["moe_intermediate_size"]),
              "router outputs": config["published"]["n_routed_experts"],
              "choice biases": (config["published"]["n_routed_experts"],),
              "shared expert": bool(config["n_shared_experts"]),
              "dense": (hidden, config["intermediate_size"]),
              "qkv full": ref.widths(spec, "full")["qkv"],
              "qkv window": ref.widths(spec, "window")["qkv"],
              "o": (heads * config["v_head_dim"], hidden),
              "sinks": (heads,),
              "window": config["sliding_window"],
              "rotated": int(config["partial_rotary_factor"]
                             * config["head_dim"]),
              "theta": (config["rope_theta"], config["swa_rope_theta"]),
              "value scale": config["attention_value_scale"],
              "experts a token": config["num_experts_per_tok"],
              "tokens": want["window_tokens"]}
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
        "moe": moe, "peer_block": int(sim.peer_block),
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
    its logits [b, T, V], its router's choices [L, b*T, k] and the mean
    probability its window layers' queries give their sinks, through the
    model the round itself runs."""
    import jax
    import jax.numpy as jnp

    from biscotti_tpu.models import mimo_v2

    model, cfg = sim.model, sim.model.info["config"]

    @jax.jit
    def view(w, x, frozen):
        params = model.unravel(w)
        experts, _ = mimo_v2.routing(cfg, params, x, frozen)
        return (model.apply_flat(w, x, frozen), experts,
                mimo_v2.sink_mass(cfg, params, x, frozen))

    logits, experts, mass = view(jnp.asarray(w, jnp.float32),
                                 jnp.asarray(x_val), sim.frozen)
    return np.asarray(logits, np.float64), np.asarray(experts), float(mass)


def reference_view(spec, frozen, w, x_val, dtype, variant=None):
    """The reference's: logits, and per sparse layer the chosen experts
    [N, k] and what all the experts were chosen by (s + b) [N, E_all]."""
    import jax.numpy as jnp

    from benchmark.reference import mimo_v2 as ref

    variant = {k: v for k, v in (variant or {}).items() if k != "store"}
    _, run = ref.compiled(spec, dtype, variant)
    logits, picks = run(frozen, jnp.asarray(w, dtype), jnp.asarray(x_val))
    return (np.asarray(logits, np.float64),
            [np.asarray(e) for e, _ in picks],
            [np.asarray(p, np.float64) for _, p in picks])


def leaf_gaps(spec, got, ref):
    """|got - ref| (L2) of every adapter leaf over the larger of that
    leaf's reference norm and the median leaf's."""
    from benchmark.reference import mimo_v2 as rq

    ref_leaves = rq.leaves(spec, np.asarray(ref, np.float64))
    got_leaves = rq.leaves(spec, np.asarray(got, np.float64))
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
            "update_norm": float(np.linalg.norm(ref["agg"])),
            "sink_mass": got.get("sink_mass"),
            "delta_norms": [float(np.linalg.norm(row))
                            for row in ref["deltas"][:4]]},
        "accept_beyond_ties": len(beyond),
        "stake_mismatch": int(np.sum(ref["stake_next"]
                                     != got["stake_next"])),
        "router_flips_beyond_ties": flips,
        "w_next_leaf_gap": max(gaps.values()),
        "err_gap": abs(ref["err"] - got["err"]),
        "logit_gap": logit_gap(got["logits"], logits),
    }


def check(record, control=None, limits=None):
    """Hold what the checked rounds of the timed object returned to the
    reference: [(name, value, limit, ok)]. `control` names one of
    CONTROLS: the reference with that departure then stands in the
    program's place (tests and limit-setting)."""
    import jax

    from benchmark.reference import mimo_v2 as ref

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
            got["logits"], got["experts"], got["sink_mass"] = program_view(
                sim, got["w_in"], x_val)
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
                view = None
                if "store" not in variant:
                    # a departure of the forward: its logits first (two
                    # windows, seconds). Where they alone are over the
                    # limit the control is not correct already, and the
                    # 21 gradients of its round (a compile a variant) are
                    # not computed
                    view = reference_view(spec, sim.frozen, got["w_in"],
                                          x_val, dtype, variant)
                    gap = logit_gap(view[0], truth_view[0])
                    if limits["logit_gap"] is not None \
                            and gap > limits["logit_gap"]:
                        flips = router_flips(view[1], truth_view[1],
                                             truth_view[2],
                                             float(mix["router_band"]))[0]
                        for name, value in (
                                ("logit_gap", gap),
                                ("router_flips_beyond_ties", flips)):
                            worst[name] = max(worst.get(name, 0), value)
                        record.setdefault("detail", []).append(
                            {"control": control, "stopped_at": "logit_gap"})
                        continue
                low = ref.reference_round(
                    spec, rnd, record["seed"], got["it"], got["w_in"],
                    got["stake_in"], sim.frozen, shard_rows, x_val, y_val,
                    dtype, variant=variant)
                logits, experts, _ = view or reference_view(
                    spec, sim.frozen, ref.bf16(got["w_in"]), x_val, dtype,
                    variant)
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
            record.setdefault("detail", []).append(dict(
                found.pop("_detail"), peer_block=record["peer_block"]))
            for name, value in found.items():
                worst[name] = max(worst.get(name, 0), value)
    record["check_s"] = time.perf_counter() - t0
    return [(name, value, limits[name],
             limits[name] is not None and value <= limits[name])
            for name, value in worst.items()]
