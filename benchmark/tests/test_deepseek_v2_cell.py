"""The `device_round_mla` driver end to end on the CPU at a tiny mix (the
chip check lifted here only), each control coming out not correct, the
attention core's FLOP count against XLA's own, and the new cell's files
found by the harness with no edit to a file that was there."""

import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "deepseek_v2_fedlora.device_round"
NEW_METRICS = {"mla_core_ms.device", "mla_proj_ms.device",
               "dsv2_experts_ms.device", "dsv2_router_ms.device",
               "mla_core_flops_share.device"}


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_mla", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """A DeepSeekV2Config in the published config.json's keys, as a
    configuration file states them."""
    return {
        "hidden_size": cfg.hidden, "num_attention_heads": cfg.heads,
        "q_lora_rank": cfg.q_rank, "kv_lora_rank": cfg.kv_rank,
        "qk_nope_head_dim": cfg.nope, "qk_rope_head_dim": cfg.rope,
        "v_head_dim": cfg.v_dim, "num_hidden_layers": cfg.layers,
        "first_k_dense_replace": len(cfg.dense_layers),
        "intermediate_size": cfg.dense_width,
        "moe_intermediate_size": cfg.expert_width,
        "n_shared_experts": cfg.shared_experts,
        "n_routed_experts": cfg.experts_held, "vocab_size": cfg.vocab,
        "n_group": cfg.groups, "topk_group": cfg.groups_kept,
        "num_experts_per_tok": cfg.top_k,
        "routed_scaling_factor": cfg.routed_scale,
        "norm_topk_prob": cfg.norm_topk, "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling, type="yarn"),
        "rms_norm_eps": cfg.eps,
        "published": {"n_routed_experts": cfg.num_experts},
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length,
                  "held_first_expert": cfg.first_expert},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny DeepSeek-V2 cell: new files and
    new entries only."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("mla_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny", "deepseek_v2_tiny")
    config = published(model.info["config"], model.d_in)
    config["model"].update(num_params=model.num_params,
                           frozen_params=lm.frozen_count(model))
    config.update(
        name="deepseek_v2_tiny", source="a test", reduced={}, assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny",
                  "model_name": "deepseek_v2_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.05},
        # float32 program against the float64 reference
        limits={"tiny_mla": {"w_next_leaf_gap": 1e-4, "err_gap": 0.04,
                             "logit_gap": 1e-4}})
    with open(here / "configs" / "deepseek_v2_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_mla_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_mla", trace_seconds=1)
    with open(here / "traffic" / "tiny_mla.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "deepseek_v2_tiny", "source": "a test",
                             "file":
                             "benchmark/configs/deepseek_v2_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.mla", "config": "deepseek_v2_tiny",
         "traffic": "tiny_mla", "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.mla")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.mla")
    driver = grown.load_module("drivers", "device_round_mla")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_mla_driver_end_to_end(grown):
    result = grown.run_cell("tiny.mla", 2**31 + 4321, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and says so


def test_sound_run_passes_every_check_and_counts_its_routing(record):
    driver, rec, sound = record
    assert all(ok for *_, ok in sound), sound
    assert {name for name, *_ in sound} == set(driver.LIMITS)
    json.dumps([{n: v for n, v, *_ in sound}, rec["detail"],
                rec["end_to_end"], rec["moe"]])
    moe = rec["moe"]
    assert all(v == 0 for v in moe["tokens_dropped"])
    assert all(v >= 1 for v in moe["load_max_over_mean"])
    # three experts a token out of 2 kept groups of 4: between 1 and 2
    assert all(1 <= v <= 2 for v in moe["groups_kept"])
    made = moe["assignments_held"][0]
    assert 0 < made < 8 * 2 * 16 * 3 * 2


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("five_experts", "router_flips_beyond_ties"),
    ("no_groups", "router_flips_beyond_ties"),
    ("renormalised", "logit_gap"),
    ("no_scale", "logit_gap"),
    ("no_shared_rope", "logit_gap"),
    ("no_inner_norms", "logit_gap"),
    ("no_mscale", "logit_gap"),
    ("no_shared", "logit_gap"),
])
def test_each_control_comes_out_not_correct(record, control, by):
    driver, rec, _ = record
    found = driver.check(rec, control=control)
    failed = {name for name, *_, ok in found if not ok}
    assert by in failed, (control, found)
    # what benchmark/controls.py prints of it: plain numbers
    json.dumps([{n: v for n, v, *_ in found}, rec.pop("detail")])


def test_a_tie_between_groups_is_a_tie_and_another_router_is_not():
    """The group-limited router's second cut: a program that kept another
    of two near-equal groups and then chose as the reference would have
    differs within ties; one that ignores the groups, or leaves a slot
    empty, does not."""
    import numpy as np

    run = load_run(os.path.join(ROOT, "benchmark"))
    driver = run.load_module("drivers", "device_round_mla")
    rng = np.random.default_rng(0)
    n, experts, groups, kept, k = 200, 32, 8, 3, 5
    probs = np.exp(rng.normal(size=(n, experts)))
    probs /= probs.sum(axis=1, keepdims=True)

    def route(rounding=0.0, limited=True, k=k):
        chosen = []
        for row in probs:
            row = row * (1 + rounding * rng.normal(size=row.shape))
            if limited:
                best = row.reshape(groups, -1).max(axis=1)
                keep = np.argsort(-best, kind="stable")[:kept]
                row = np.where(np.isin(np.arange(experts)
                                       // (experts // groups), keep), row, 0)
            chosen.append(np.argsort(-row, kind="stable")[:k])
        return np.array(chosen)

    want = route()

    def judged(got):
        return driver.router_flips([got], [want], [probs], 0.1, groups, kept)

    assert judged(want)[:2] == (0, 0.0)
    rounded = route(rounding=0.02)
    beyond, worst, differ = judged(rounded)
    assert beyond == 0 and 0 < worst <= 0.1 and differ.sum() >= 5
    # some of those kept ANOTHER group: no expert of theirs is near the
    # reference's own k-th probability, only near the other set's
    groups_of = lambda rows: [set((r // 4).tolist()) for r in rows]  # noqa: E731
    assert any(a != b for a, b in zip(groups_of(rounded), groups_of(want)))
    assert judged(route(limited=False))[0] > n // 4
    assert judged(route(k=k - 1))[0] == n


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.mla")
    driver = grown.load_module("drivers", "device_round_mla")
    from benchmark.compile_meter import CompileMeter

    wrong = dict(cell, config=dict(
        cell["config"], model=dict(cell["config"]["model"], num_params=7)))
    with pytest.raises(RuntimeError, match="the configuration states 7"):
        driver.run(cell=wrong, fields=grown.biscotti_fields(cell, 1),
                   seconds=0.1, trace_dir=None, meter=CompileMeter(),
                   t0=0.0)


def test_core_flops_against_xla():
    """2 d + 2 e a causal pair forward: XLA counts the whole square of
    the `einsum` form, T^2 pairs where the mask lets T (T + 1) / 2
    through; the backward's products are twice the forward's, and the
    count's 2.5 adds the scores made again."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops.deepseek_v2 import (core_forward_flops,
                                             core_step_flops)

    windows, heads, t, d, e = 2, 3, 64, 24, 16

    def core(q, k, v):
        s = jnp.einsum("whid,whjd->whij", q, k)
        return jnp.sum(jnp.einsum("whij,whje->whie", s, v))  # no softmax

    shapes = [jax.ShapeDtypeStruct((windows, heads, t, width), jnp.float32)
              for width in (d, d, e)]
    forward = jax.jit(core).lower(*shapes).compile().cost_analysis()["flops"]
    both = jax.jit(jax.value_and_grad(core, argnums=(0, 1, 2))).lower(
        *shapes).compile().cost_analysis()["flops"]
    want = core_forward_flops(windows, heads, t, d, e)
    square = want * 2 * t / (t + 1)  # all T^2 pairs
    assert square <= forward <= 1.05 * square
    assert 3 * square <= both <= 1.05 * 3 * square  # forward + 2 x
    assert core_step_flops(windows, heads, t, d, e) == 7 * want // 2


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["mix"]["driver"] == "device_round_mla"
    names = [m["name"] for m in cell["per_layer"]]
    assert NEW_METRICS <= set(names) and len(names) == 17 + 5
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens_dsv2"
    assert fields["noising"] is True
    # the other cells read none of the new metrics
    for other in ("emnist_softmax.device_round",
                  "laguna_fedlora.device_round"):
        found = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert not NEW_METRICS & found
    # every reader file loads, and finds nothing in an empty record
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read({}) is None
    driver = run.load_module("drivers", "device_round_mla")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended at the END of their lists
    assert bench["configs"][-1]["name"] == "deepseek_v2_fedlora"
    assert bench["workloads"][-1]["name"] == CELL
    assert {m["name"] for m in bench["per_layer"][-5:]} == NEW_METRICS
    assert all(m["workloads"] == [CELL] for m in bench["per_layer"][-5:])


def test_the_configuration_carries_every_published_number():
    """Every number of the catalog row's `config` under the same key,
    unchanged but for the three listed in `reduced`; the driver's sizes
    come out of the built model."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek_v2_fedlora.json")) as f:
        config = json.load(f)
    from biscotti_tpu.models import deepseek_v2, lm

    preset = deepseek_v2.PRESETS["deepseek_v2_fedlora"]
    mine = published(preset, 1024)
    for key, value in mine.items():
        if key in ("published", "adapters", "model", "rope_scaling"):
            continue
        assert config[key] == value, key
    for key, value in mine["rope_scaling"].items():
        assert config["rope_scaling"][key] == value, key
    assert preset.rank == config["adapters"]["rank"]
    assert preset.alpha == config["adapters"]["alpha"]
    assert preset.num_experts == config["published"]["n_routed_experts"]
    model = deepseek_v2.deepseek_v2_model("deepseek_v2_fedlora", preset,
                                          1024)
    assert model.num_params == config["model"]["num_params"] == 5166080
    assert lm.frozen_count(model) == config["model"]["frozen_params"] \
        == 5166269440
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2")
    assert config["source"].startswith(row["source_url"])
    changed = {k for k, v in row["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in changed}
