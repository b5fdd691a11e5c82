#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers that `correct`
compares: from sound runs of the program, and from the control (the plain
reference computed in the precision below the one the configuration
states, put in the program's place). The limits in the drivers were set
from these readings (PERF.md section 2). The benchmark's own runs never
run this.

    python3 benchmark/controls.py --workload <cell> --seeds 11,12,13 \
        [--control bfloat16,bfloat16_f32acc] [--control-first 3] [--seconds 1]

One process, one seed after another (the shards are made once); prints one
JSON line per seed. `--control-first K` runs the controls on the first K
seeds only.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark import run as bench_run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="bfloat16")
    ap.add_argument("--control-first", type=int, default=None)
    ap.add_argument("--seconds", type=float, default=1.0)
    ns = ap.parse_args(argv)
    cell = bench_run.load_cell(ns.workload)
    jax, _, meter = bench_run.start_jax(cell["chips"])
    driver = bench_run.load_module("drivers", cell["mix"]["driver"])
    for at, seed in enumerate(int(s) for s in ns.seeds.split(",")):
        record = driver.run(
            cell=cell, fields=bench_run.biscotti_fields(cell, seed),
            seconds=ns.seconds,
            trace_dir=None, meter=meter, t0=time.time())
        sound = driver.check(record)
        line = {"seed": seed, "attempted": record["attempted"],
                "failed": record["failed"],
                "end_to_end": record["end_to_end"],
                "sound": {n: v for n, v, *_ in sound},
                "sound_correct": all(ok for *_, ok in sound),
                "check_s": record.get("check_s"),
                "detail": record.pop("detail", None), "controls": {}}
        controls = [] if (ns.control_first is not None
                          and at >= ns.control_first) \
            else filter(None, ns.control.split(","))
        for control in controls:
            found = driver.check(record, control=control)
            line["controls"][control] = {
                "values": {n: v for n, v, *_ in found},
                "correct": all(ok for *_, ok in found),
                "detail": record.pop("detail", None)}
        print(json.dumps(line), flush=True)
        del record
        gc.collect()  # the Simulator's arrays leave the chip before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
