"""The `device_round_gdn` driver end to end on the CPU at a tiny mix (the
chip check lifted here only), each control coming out not correct, the
rule's FLOP count against XLA's own, and the new cell's files found by the
harness with no edit to a file that was there."""

import importlib.util
import json
import os
import shutil

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "qwen3_next_fedlora.device_round"
CONFIG = "qwen3_next_80b_a3b_fedlora"
NEW_METRICS = ["gdn_rule_ms.device", "gdn_proj_ms.device",
               "gdn_mix_ms.device", "q3n_attention_ms.device",
               "q3n_experts_ms.device", "q3n_router_ms.device",
               "gdn_rule_roofline_share.device", "q3n_round_mfu.device"]


def load_run(here):
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_under_test_gdn", os.path.join(here, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def published(cfg, length):
    """A Qwen3NextConfig in the published config.json's keys, as a
    configuration file states them."""
    return {
        "hidden_size": cfg.hidden, "num_hidden_layers": cfg.layers,
        "full_attention_interval": cfg.full_attention_interval,
        "num_attention_heads": cfg.heads,
        "num_key_value_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.rotary_factor,
        "rope_theta": int(cfg.rope_theta),
        "linear_num_key_heads": cfg.key_heads,
        "linear_num_value_heads": cfg.value_heads,
        "linear_key_head_dim": cfg.key_dim,
        "linear_value_head_dim": cfg.value_dim,
        "linear_conv_kernel_dim": cfg.conv,
        "num_experts": cfg.experts_held,
        "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
        "moe_intermediate_size": cfg.expert_width,
        "shared_expert_intermediate_size": cfg.shared_width,
        "rms_norm_eps": cfg.eps, "tie_word_embeddings": False,
        "vocab_size": cfg.vocab,
        "published": {"num_experts": cfg.num_experts},
        "adapters": {"rank": cfg.rank, "alpha": cfg.alpha},
        "model": {"window_tokens": length, "rule_chunk": cfg.chunk,
                  "held_first_expert": cfg.first_expert},
    }


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    """A copy of the benchmark with a tiny delta-net cell: new files and
    new entries only."""
    from biscotti_tpu.models import lm
    from biscotti_tpu.models.zoo import model_for_dataset

    tmp = tmp_path_factory.mktemp("gdn_cell")
    here = tmp / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), here,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    model = model_for_dataset("lm_tokens_tiny", "qwen3_next_tiny")
    config = published(model.info["config"], model.d_in)
    config["model"].update(num_params=model.num_params,
                           frozen_params=lm.frozen_count(model))
    config.update(
        name="qwen3_next_tiny", source="a test", reduced=[], assumed=[],
        guarantees=[],
        biscotti={"dataset": "lm_tokens_tiny",
                  "model_name": "qwen3_next_tiny",
                  "num_nodes": 12, "num_verifiers": 1, "num_miners": 1,
                  "num_noisers": 1, "sample_percent": 0.7, "epsilon": 1.0,
                  "batch_size": 2, "defense": "KRUM", "learning_rate": 0.1,
                  "grad_clip": 0.005},
        # float32 program against the float64 reference; a router's tie is
        # a flip inside the band
        limits={"tiny_gdn": {"w_next_leaf_gap": 2e-4, "err_gap": 0.04,
                             "logit_gap": 2e-5,
                             "router_flips_beyond_ties": 0}})
    with open(here / "configs" / "qwen3_next_tiny.json", "w") as f:
        json.dump(config, f)
    with open(here / "traffic" / "device_round_gdn_dp.json") as f:
        mix = json.load(f)
    mix.update(name="tiny_gdn", trace_seconds=1)
    with open(here / "traffic" / "tiny_gdn.json", "w") as f:
        json.dump(mix, f)
    bench["configs"].append({"name": "qwen3_next_tiny", "source": "a test",
                             "file": "benchmark/configs/qwen3_next_tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append(
        {"name": "tiny.gdn", "config": "qwen3_next_tiny",
         "traffic": "tiny_gdn", "chips": 1, "why": "a test"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append("tiny.gdn")
    with open(tmp / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return load_run(str(here))


@pytest.fixture(scope="module")
def record(grown):
    """One sound run of the tiny cell, checked once: the reference's round
    stays on the record for every control."""
    from benchmark.compile_meter import CompileMeter

    cell = grown.load_cell("tiny.gdn")
    driver = grown.load_module("drivers", "device_round_gdn")
    record = driver.run(cell=cell, fields=grown.biscotti_fields(cell, 7),
                        seconds=0.3, trace_dir=None, meter=CompileMeter(),
                        t0=0.0)
    return driver, record, driver.check(record)


def test_gdn_driver_end_to_end(grown):
    result = grown.run_cell("tiny.gdn", 2**31 + 4321, 0.5, False,
                            require_tpu=False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"device_round_ms",
                                      "device_round_ms.p95", "setup_s"}
    assert result["device"]["platform"] == "cpu"  # and says so


def test_sound_run_passes_every_check(record):
    driver, rec, sound = record
    assert all(ok for *_, ok in sound), sound
    assert {name for name, *_ in sound} == set(driver.LIMITS)
    assert rec["peer_block"] == 8 and rec["moe"]["tokens_dropped"][0] == 0
    json.dumps([{n: v for n, v, *_ in sound}, rec["detail"],
                rec["end_to_end"], rec["peer_block"], rec["moe"]])


@pytest.mark.parametrize("control,by", [
    ("bfloat16", "w_next_leaf_gap"),
    ("no_delta", "logit_gap"),
    ("beta_one", "logit_gap"),
    ("decay_bfloat16", "logit_gap"),
    ("no_carry", "logit_gap"),
    ("no_l2norm", "logit_gap"),
    ("gate_before_norm", "logit_gap"),
    ("norm_not_zero_centred", "logit_gap"),
    ("no_output_gate", "logit_gap"),
    ("no_shared_gate", "logit_gap"),
    ("rotary_full", "logit_gap"),
    ("no_renormalise", "logit_gap"),
])
def test_each_control_comes_out_not_correct(record, control, by):
    driver, rec, _ = record
    assert control in driver.CONTROLS
    found = driver.check(rec, control=control)
    failed = {name for name, *_, ok in found if not ok}
    assert by in failed, (control, found)
    # what benchmark/controls.py prints of it: plain numbers
    json.dumps([{n: v for n, v, *_ in found}, rec.pop("detail")])


def test_every_control_of_the_issue_has_a_test():
    run = load_run(os.path.join(ROOT, "benchmark"))
    driver = run.load_module("drivers", "device_round_gdn")
    assert set(driver.CONTROLS) == {
        "bfloat16", "no_delta", "beta_one", "decay_bfloat16", "no_carry",
        "no_l2norm", "gate_before_norm", "norm_not_zero_centred",
        "no_output_gate", "no_shared_gate", "rotary_full",
        "no_renormalise"}
    # those that depart at a chunk's boundary learn the assumed chunk
    config = {"model": {"rule_chunk": 64}}
    assert driver.variant_of("no_carry", config) == {"carry": False,
                                                     "chunk": 64}
    assert driver.variant_of("decay_bfloat16", config)["chunk"] == 64
    assert driver.variant_of("no_delta", config) == {"delta": False}


def test_a_wrong_size_is_refused(grown):
    cell = grown.load_cell("tiny.gdn")
    driver = grown.load_module("drivers", "device_round_gdn")
    from benchmark.compile_meter import CompileMeter

    for key, value, said in (("num_params", 7, "states 7"),
                             ("window_tokens", 32, "the program holds"),
                             ("rule_chunk", 8, "the program holds")):
        wrong = dict(cell, config=dict(cell["config"], model=dict(
            cell["config"]["model"], **{key: value})))
        with pytest.raises(RuntimeError, match=said):
            driver.run(cell=wrong, fields=grown.biscotti_fields(cell, 1),
                       seconds=0.1, trace_dir=None, meter=CompileMeter(),
                       t0=0.0)


def test_rule_flops_against_xla():
    """The chunked form's products as XLA counts them: XLA takes the whole
    square of a chunk's (i, j) pairs, L^2 where the mask lets L (L + 1) /
    2 through; the state's three products are exact; the solve is a
    forward substitution XLA does not count as products. The backward is
    twice the forward (every product is bilinear in activations)."""
    import jax
    import jax.numpy as jnp

    from benchmark.flops import qwen3_next as count

    windows, t, g, d, r, e = 2, 128, 2, 16, 2, 24
    chunk, n = count.CHUNK, 128 // count.CHUNK

    def products(q, k, delta, wy, state):   # no decays, no solve
        kk = jnp.einsum("wngid,wngjd->wngij", k, k)
        qk = jnp.einsum("wngid,wngjd->wngij", q, k)
        out = jnp.einsum("wngrij,wngrje->wngrie",
                         jnp.broadcast_to(qk[:, :, :, None],
                                          delta.shape[:-1] + (chunk,)),
                         delta)
        ws = jnp.einsum("wngrld,wngrde->wngrle", wy, state)
        kd = jnp.einsum("wngld,wngrle->wngrde", k, delta)
        qs = jnp.einsum("wngld,wngrde->wngrle", q, state)
        return sum(jnp.sum(a) for a in (kk, out, ws, kd, qs))

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (windows, n, g, chunk, d), (windows, n, g, chunk, d),
        (windows, n, g, r, chunk, e), (windows, n, g, r, chunk, d),
        (windows, n, g, r, d, e))]
    forward = jax.jit(products).lower(*shapes).compile().cost_analysis()[
        "flops"]
    want = count.rule_forward_flops(windows, t, g, d, g * r, e)
    below = windows * t * (chunk + 1) // 2 * (4 * g * d + 2 * g * r * e)
    solve = windows * t * (chunk - 1) // 2 * 2 * g * r * (e + d)
    square = want - solve - below + below * 2 * chunk / (chunk + 1)
    assert square <= forward <= 1.05 * square
    assert count.rule_step_flops(windows, t, g, d, g * r, e) == 3 * want
    # a window shorter than the chunk is one chunk
    assert count.rule_forward_flops(1, 16, g, d, g * r, e) < \
        count.rule_forward_flops(1, 64, g, d, g * r, e) / 4 + 1e9
    # the published shapes: 4.19 MFLOP a token forward, 135 MB a stepped
    # window of least bytes (bytes bind: 0.165 ms against 0.065)
    big = (1024, 16, 128, 32, 128)
    assert round(count.rule_forward_flops(1, *big) / 1024 / 1e6, 2) == 4.19
    assert count.rule_step_bytes(1, *big) == 135004160


def test_round_flops_are_the_issues_count():
    """The products with frozen weights a token forward (ISSUE 38: about
    96 MFLOP a delta-net layer with its sparse MLP at the held share), twice
    that a stepped token, and the rule, the attention core and the
    adapters on top: a round of 21 + 2 windows."""
    from benchmark.flops.qwen3_next import (round_model_flops, rule_layers,
                                            rule_round)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        config = json.load(f)
    sparse = 2 * 2048 * 512 + 6 * 2048 * 512 + 2 * 2048 \
        + 2.5 * 6 * 2048 * 512
    delta = 2 * 2048 * 12288 + 2 * 2048 * 64 + 2 * 4096 * 2048 + sparse
    full = 2 * 2048 * 8192 + 2 * 2 * 2048 * 512 + 2 * 4096 * 2048 + sparse
    assert 90e6 < delta < 100e6
    weights = 9 * delta + 3 * full + 2 * 2048 * 37984
    least = 1024 * (21 * 2 + 2) * weights
    got = round_model_flops(config, 21, 2)
    assert least < got < 1.15 * least
    assert round_model_flops(config, 42, 4) == 2 * got
    assert rule_layers(config) == 9
    flops, moved = rule_round(config, 21, 2)
    assert flops == 9 * (21 * 3 + 2) * 1024 * 4194304
    assert 0.02 < moved / 819e9 < 0.04   # the rule's least time, s a round


def test_the_cell_is_found_with_no_edit_to_a_file_that_was_there():
    run = load_run(os.path.join(ROOT, "benchmark"))
    cell = run.load_cell(CELL)
    assert cell["chips"] == 1
    assert cell["mix"]["driver"] == "device_round_gdn"
    names = [m["name"] for m in cell["per_layer"]]
    # (at least: a later PR may widen an accepted metric to this cell)
    assert set(NEW_METRICS) <= set(names) and len(names) >= 17 + 8
    fields = run.biscotti_fields(cell, 2**31 + 9)
    assert fields["num_nodes"] == 30 and fields["batch_size"] == 1
    assert fields["dataset"] == "lm_tokens_qwen3next"
    assert fields["noising"] is True and fields["poison_fraction"] == 0.0
    mix = cell["mix"]
    assert (mix["warm_rounds"], mix["checked_rounds"],
            mix["trace_seconds"]) == (2, 1, 16)
    # the other cells read none of the new metrics
    for other in ("emnist_softmax.device_round",
                  "laguna_fedlora.device_round",
                  "deepseek_v2_fedlora.device_round",
                  "granite_h_fedlora.device_round"):
        found = {m["name"] for m in run.load_cell(other)["per_layer"]}
        assert not set(NEW_METRICS) & found
    # every reader file loads, and finds nothing in an empty record
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read({}) is None
    driver = run.load_module("drivers", "device_round_gdn")
    assert set(driver.limits_of(cell)) == set(driver.LIMITS)
    assert all(v is not None for v in driver.limits_of(cell).values())
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended AFTER everything PR 37's file had, together and in the
    # issue's order (a later PR appends after them: "last" would turn this
    # test red, as the sibling cells' of the same name are)
    config = [c["name"] for c in bench["configs"]].index(CONFIG)
    assert config >= 4 and bench["configs"][config]["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert [w["name"] for w in bench["workloads"]].index(CELL) >= 4
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert first >= 42 and names[first:first + 8] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "device_round_ms"
               and m["layer"] == "language model"
               and m["source"] == "device_trace"
               for m in bench["per_layer"][first:first + 8])
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200 and len(entry.get(
            "source", "")) <= 200


def test_the_configuration_carries_every_published_number():
    """Every key of the catalog row's `config` under the same key,
    unchanged but for the three in `reduced`; the driver's sizes come out
    of the built model; every assumption of ISSUE 38 is in the file."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CONFIG}.json")) as f:
        config = json.load(f)
    from biscotti_tpu.models import lm, qwen3_next

    preset = qwen3_next.PRESETS["qwen3_next_fedlora"]
    for key, value in published(preset, 1024).items():
        if key not in ("adapters", "model", "published"):
            assert config[key] == value, key
    assert preset.rank == config["adapters"]["rank"]
    assert preset.alpha == config["adapters"]["alpha"]
    assert config["model"]["rule_chunk"] == preset.chunk == 64
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    model = qwen3_next.qwen3_next_model("qwen3_next_fedlora", preset, 1024)
    assert model.num_params == config["model"]["num_params"] == 2605056
    assert lm.frozen_count(model) == config["model"]["frozen_params"] \
        == 5424460992
    assert "ixteen v5e chips" in config["deployment"]
    said = " ".join(config["assumed"])
    for word in ("1 + w", "l2-normalised", "128^-0.5", "second half",
                 "sigmoid(x w_sg)", "BEFORE the gate",
                 "multi-token-prediction", "chunk of the delta rule",
                 "uniform in (0, 16]", "dt_bias", "N(0, 1/4)"):
        assert word in said, word
    assert any("all 512 experts and no routed token is dropped" in g
               for g in config["guarantees"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert config["source"].startswith(row["source_url"])
    assert {k for k, v in row["config"].items() if config.get(k) != v} \
        == set(config["reduced"])
