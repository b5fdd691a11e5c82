#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds everything by the names in BENCHMARK.json: the cell's configuration
(`benchmark/configs/<config>.json`), its traffic mix
(`benchmark/traffic/<traffic>.json`), the mix's driver
(`benchmark/drivers/<driver>.py`) and, in a traced run, one reader per
per-layer metric (`benchmark/layer_metrics/<metric>.py`). Refuses to start
without a TPU or with fewer chips than the cell asks for: exit 2, no
result line. The last line of standard output is the result object.
"""

import time

T0 = time.time()  # set-up is counted from here, before anything is loaded

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# import as the package `benchmark` from the checkout's root; the script's
# own directory leaves the path (its trace.py would shadow the library's)
sys.path[:] = [ROOT] + [p for p in sys.path
                        if os.path.abspath(p or os.getcwd())
                        not in (HERE, ROOT)]
SEED_MOD = 2**31 - 1  # the program hands its seed to the device as int32


def note(msg):
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload, bench=None):
    """The cell's entry, configuration, mix and the metrics it reports."""
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, entry["file"])
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if "workloads" not in m or workload in m["workloads"]]

    end_to_end = mine(bench["end_to_end"])
    reported = {m["name"] for m in end_to_end}
    # a per-layer metric belongs to the cells that report what it moves
    return {"name": workload, "chips": cell["chips"], "config": config,
            "mix": mix, "end_to_end": end_to_end,
            "per_layer": [m for m in mine(bench["per_layer"])
                          if m["moves"] in reported]}


def biscotti_fields(cell, seed):
    """The BiscottiConfig fields this cell fixes: the configuration's,
    then the mix's protocol switches and its cut of the scale; `seed` is
    --seed folded into what an int32 holds."""
    fields = dict(cell["config"]["biscotti"])
    fields.update(cell["mix"].get("switches", {}))
    fields.update({k: v for k, v in cell["mix"].get("scale", {}).items()
                   if k != "why"})
    fields["seed"] = int(seed) % SEED_MOD
    return fields


def start_jax(chips, require_tpu=True):
    """x64 on, the compile cache by the repo's one rule, and the device:
    no TPU or too few chips is exit 2 with nothing on standard output."""
    import jax

    jax.config.update("jax_enable_x64", True)
    if require_tpu:
        if jax.default_backend() != "tpu":
            note(f"jax.default_backend() is {jax.default_backend()!r}, not "
                 "'tpu': the benchmark measures only on the chip")
            raise SystemExit(2)
        if len(jax.devices()) < chips:
            note(f"the cell asks for {chips} chips, JAX sees "
                 f"{len(jax.devices())}")
            raise SystemExit(2)
    from biscotti_tpu.utils import jaxenv

    cache = jaxenv.configure_compile_cache()
    from benchmark.compile_meter import CompileMeter

    return jax, cache, CompileMeter()


def device_object(jax):
    """The device as JAX reports it. `memory_peak_bytes` is the
    allocator's own peak (`peak_bytes_in_use`) on the fullest chip, as it
    is; what the runtime holds reserved as the window closes
    (`bytes_reserved`) stands beside it under its own name."""
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats),
            "memory_reserved_bytes": max(int(s.get("bytes_reserved", 0))
                                         for s in stats)}


def run_cell(workload, seed, seconds, trace, require_tpu=True, bench=None,
             trace_dir=None):
    """One run; returns the result object (the caller prints it)."""
    cell = load_cell(workload, bench)
    jax, cache, meter = start_jax(cell["chips"], require_tpu)
    note(f"{workload} seed={seed} seconds={seconds} trace={trace} "
         f"cache={cache}")
    driver = load_module("drivers", cell["mix"]["driver"])
    if trace and trace_dir is None:
        trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    record = driver.run(cell=cell, fields=biscotti_fields(cell, seed),
                        seconds=float(seconds),
                        trace_dir=trace_dir if trace else None,
                        meter=meter, t0=T0)
    # memory first: the reference below must not count as the program's
    device = device_object(jax)
    reduction = None
    if trace:
        from benchmark import trace as trace_reduction

        reduction = trace_reduction.reduce_xplane(
            trace_reduction.newest_xplane(trace_dir))
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
    record["trace"] = reduction
    record["device"] = device
    # the readers first: the check below may drive the program again
    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = load_module("layer_metrics", m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if m["name"] in record["end_to_end"]:
                metrics[m["name"]] = {
                    "value": float(record["end_to_end"][m["name"]]),
                    "unit": m["unit"]}
    checks = driver.check(record)
    for name, value, limit, ok in checks:
        print(f"check {name} value={value!r} limit={limit!r} "
              f"{'ok' if ok else 'NOT OK'}", flush=True)
    for row in record.get("detail", []):
        print(f"detail {json.dumps(row)}", flush=True)
    result = {"correct": bool(checks) and all(ok for *_, ok in checks),
              "attempted": int(record["attempted"]),
              "failed": int(record["failed"]),
              "metrics": metrics, "device": device}
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["compile"] = meter.totals()
    result["check_s"] = record.get("check_s")
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ns = ap.parse_args(argv)
    result = run_cell(ns.workload, ns.seed, ns.seconds, bool(ns.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
