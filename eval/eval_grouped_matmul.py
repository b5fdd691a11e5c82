#!/usr/bin/env python
"""One grouped product alone, at the shapes a language model's round sends
it: the compiler's `ragged-dot` against ops/grouped_matmul.py at each row
tile, timed from the DEVICE trace (per-program durations).

The round's three products a sparse layer and their activation backward
are, a peer block of 3 of Laguna's: `[15,360, 3,072] x [64, 3,072, 1,024]`
(`w_gate`, `w_up`), `[15,360, 1,024] x [64, 1,024, 3,072]` (`w_down`), the
two read transposed (the backward), and all four at 30,720 rows (the uncut
side of ops/moe.py's `lax.cond`). The groups are drawn as the cell draws
them: 64 groups, 120 rows the mean, the fullest about three times that,
the rest of the buffer in no group. `--shapes deepseek_v2` is that model's
block: 40 groups of 115 rows in buffers of 9,216 and 18,432 rows, K | N =
5,120 | 1,536 and 1,536 | 5,120 (what a call pays for its tail: PERF.md
section 6, PR 32). The compiler's call is timed both ways: with the tail's
rows added to the last group (the program before PR 28) and left out.

Needs the chip. Artifact: <out>/grouped_matmul_<shapes>.json, and the table
on standard error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 8
# held experts, hidden size, expert width, (the cut buffer of a peer block
# of 3, the uncut one), the held rows a uniform router sends
SHAPES = {"laguna": (64, 3072, 1024, (15360, 30720), 7680),
          "deepseek_v2": (40, 5120, 1536, (9216, 18432), 4608)}


def draw_sizes(rng, groups, held_rows):
    """`groups` sizes that add up to `held_rows`, the fullest about three
    times the mean (a Zipf vocabulary behind a random router, PERF.md
    section 5)."""
    import numpy as np

    p = np.exp(0.6 * rng.normal(size=groups))
    return rng.multinomial(held_rows, p / p.sum()).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out")
    ap.add_argument("--seed", type=int, default=28)
    ap.add_argument("--shapes", choices=sorted(SHAPES), default="laguna")
    ap.add_argument("--column-tiles", default="",
                    help="also time the kernel at these column tiles "
                         "(the program's own choice is always timed)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from biscotti_tpu.ops import grouped_matmul as gm
    from biscotti_tpu.utils import jaxenv
    from biscotti_tpu.utils.profiling import device_program_ms, device_trace

    jaxenv.configure_compile_cache()
    jax.config.update("jax_enable_x64", True)  # as every entry point has it
    if jax.default_backend() != "tpu":
        print("a device time comes only from the chip", file=sys.stderr)
        return 2
    groups, hidden, width, buffers, held_rows = SHAPES[args.shapes]
    rng = np.random.default_rng(args.seed)
    extra = [int(t) for t in args.column_tiles.split(",") if t]
    rows = []
    for c in buffers:
        sizes = draw_sizes(rng, groups, held_rows)
        padded = sizes.copy()
        padded[-1] += c - sizes.sum()
        for k, n, transposed in ((hidden, width, False),
                                 (width, hidden, False),
                                 (hidden, width, True),
                                 (width, hidden, True)):
            # transposed: xs [C, k] against w [E, n, k], the backward of
            # the product whose weights are [E, n, k]
            dt = jnp.bfloat16
            xs = jnp.asarray(rng.normal(size=(c, k)), dt)
            w = jnp.asarray(rng.normal(size=(groups, n, k) if transposed
                                       else (groups, k, n)) / np.sqrt(k),
                            dt)
            programs = {}

            def compilers(xs, w, sz):
                if not transposed:
                    return jax.lax.ragged_dot(
                        xs, w, sz, preferred_element_type=jnp.float32)
                primal = jnp.zeros((c, n), dt)  # the round's own backward
                return jax.vjp(lambda x: jax.lax.ragged_dot(
                    x, w, sz, preferred_element_type=jnp.float32),
                    primal)[1](xs.astype(jnp.float32))[0]

            def add(label, fn):
                fn.__name__ = fn.__qualname__ = (
                    f"{label}_{c}_{k}_{n}_{int(transposed)}")
                programs[label] = (jax.jit(fn), fn.__name__)

            for label, sz in (("compiler_tail_in_last_group", padded),
                              ("compiler", sizes)):
                add(label, lambda xs, w, sz=jnp.asarray(sz):
                    compilers(xs, w, sz))
            for tm in gm.ROW_TILES:
                own = gm.column_tile(c, k, n, dt, tm)  # the program's
                if own is None:  # no column tile fits VMEM at this row tile
                    continue
                for tn in sorted({own, *extra}):
                    if n % tn:
                        continue

                    def kernel(xs, w, tm=tm, tn=tn):
                        with jax.enable_x64(False):
                            plan = gm.schedule(jnp.asarray(sizes), c, tm)
                        return gm._call(
                            False, *plan, xs, w, tm=tm, tn=tn,
                            transposed=transposed,
                            out_dtype=dt if transposed else jnp.float32)

                    add(f"kernel_tm{tm}_tn{tn}", kernel)
            want = np.asarray(programs["compiler"][0](xs, w), np.float32)
            trace_dir = tempfile.mkdtemp(prefix="grouped_trace_")
            worst = {}
            for label, (fn, _) in programs.items():
                got = np.asarray(jax.block_until_ready(fn(xs, w)),
                                 np.float32)  # compiles, and the result
                held = int(sizes.sum())
                worst[label] = float(np.max(np.abs(got[:held] - want[:held]))
                                     / np.max(np.abs(want[:held])))
            with device_trace(trace_dir):
                for fn, _ in programs.values():
                    for _ in range(ITERS):
                        out = fn(xs, w)
                    jax.block_until_ready(out)
            ms = device_program_ms(trace_dir)
            row = {"rows": c, "k": k, "n": n, "transposed": transposed,
                   "held_rows": int(sizes.sum()),
                   "max_over_mean": float(sizes.max() / sizes.mean()),
                   "visits": {tm: int(gm.tile_visits(jnp.asarray(sizes), tm))
                              for tm in gm.ROW_TILES},
                   "device_ms": {}, "gap_to_compiler": worst}
            for label, (_, name) in programs.items():
                took = sorted(ms.get(f"jit_{name}", []))
                row["device_ms"][label] = (round(took[len(took) // 2], 4)
                                           if took else None)
            rows.append(row)
            print(json.dumps(row), file=sys.stderr, flush=True)

    os.makedirs(args.out, exist_ok=True)
    payload = {"experiment": "grouped_matmul", "shapes": args.shapes,
               **jaxenv.device_info(),
               "timing": "median per-program device duration, "
                         f"{ITERS} calls, jax.profiler trace",
               "rows": rows}
    with open(os.path.join(args.out, f"grouped_matmul_{args.shapes}.json"),
              "w") as fp:
        json.dump(payload, fp, indent=1)
    best = [min((v, k) for k, v in r["device_ms"].items()
                if v and k.startswith("kernel"))
            for r in rows]
    print(json.dumps({"experiment": "grouped_matmul",
                      "compiler_over_best_kernel": [
                          round(r["device_ms"]["compiler_tail_in_last_group"]
                                / b[0], 2) for r, b in zip(rows, best)],
                      "best": [b[1] for b in best]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
