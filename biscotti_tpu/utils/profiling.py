"""Device-time and phase profiling.

Two instruments (SURVEY §5.1 — the reference's only timing signal is
wall-clock deltas between log lines, parsed after the fact by
eval_performance/parseLogs.py):

* `device_trace(log_dir)` — context manager around `jax.profiler` so any
  run (bench, sim, peer) can capture a real XLA device trace;
  `device_program_ms(log_dir)` reduces it to per-program device times.
* `PhaseClock` — cheap cumulative wall-clock accounting by phase name
  (sgd / noise / crypto_commit / share_gen / verify_wait / miner_verify /
  recovery / transport). The peer agent carries one and returns the totals
  with its result, which eval/eval_cost_breakdown.py turns into the
  per-phase cost table (the reference's eval_cost_breakdown.pdf
  equivalent, ref: usenix-eval/).

The two meet in `annotation(name)`: every `PhaseClock.phase` (and so every
`Telemetry.span`, which times through it) also opens a
`jax.profiler.TraceAnnotation` named `biscotti:<name>`, so a device trace
shows the program's own spans on the profiler's clock, beside the device's
operations (docs/OBSERVABILITY.md, "Device trace"). With the profiler off
that is one flag check. This module imports nothing but the stdlib:
`telemetry` sits on the config/tooling import path.
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
from typing import Dict, List

TRACE_PREFIX = "biscotti:"  # the program's spans in a profiler trace


def annotation(name: str):
    """`jax.profiler.TraceAnnotation("biscotti:<name>")` where this process
    has imported jax already, a `nullcontext` where it has not (a process
    without jax has no profiler to write to, and importing it here would
    tax every CLI start)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(TRACE_PREFIX + name)


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Capture a jax.profiler device trace into `log_dir` (an
    `.xplane.pb` under `plugins/profile/<time>/`; read it back with
    `device_program_ms`). A profiler that will not start or stop raises:
    a caller that asked for a trace must not get an untraced run. Python's
    own tracer stays off: it slows the host it is meant to observe, and the
    spans worth reading are the `biscotti:` annotations."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def device_program_ms(trace_dir: str) -> Dict[str, List[float]]:
    """Device durations (ms) of every XLA program in the newest trace
    under `trace_dir`, keyed by program name (`jit_<fn>`), one entry per
    execution — read from the `.xplane.pb`, the profiler's own record,
    with nothing but JAX. Layout as seen on the v5e (PR 21): device
    planes are `/device:TPU:n`; their "XLA Modules" line carries one
    event per program run, named `jit_<fn>(<fingerprint>)` ("XLA Ops"
    holds the per-op events). Raises when the trace holds no device
    plane: a CPU trace has no device time to report."""
    import glob
    import os

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = [p for p in data.planes if p.name.startswith("/device:TPU")]
    if not planes:
        raise RuntimeError(
            f"{paths[-1]} has no /device:TPU plane (planes: "
            f"{[p.name for p in data.planes]}) — device time comes only "
            "from a trace taken on the chip")
    out: Dict[str, List[float]] = {}
    for line in planes[0].lines:
        if line.name != "XLA Modules":
            continue
        for ev in line.events:
            out.setdefault(ev.name.split("(")[0], []).append(
                ev.duration_ns / 1e6)
    return out


class _Timing:
    """What `PhaseClock.phase` yields: `seconds` is set as the phase ends."""

    __slots__ = ("seconds",)

    def __init__(self):
        self.seconds = 0.0


class PhaseClock:
    """Cumulative per-phase wall-clock accounting."""

    def __init__(self):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self._lock = threading.Lock()

    def add(self, name: str, dt: float) -> None:
        """Charge `dt` seconds to `name` — the ONE accounting invariant.
        Locked: shards are drawn from several threads (data/datasets.py),
        and a read-modify-write of two dicts loses calls without it."""
        with self._lock:
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time the body, charge it to `name`, and show it in a profiler
        trace as `biscotti:<name>`. THE one place a span is timed:
        `Telemetry.span` runs its body through here and reads the yielded
        timing's `seconds` afterwards."""
        timing = _Timing()
        with annotation(name):
            t0 = time.perf_counter()
            try:
                yield timing
            finally:
                timing.seconds = time.perf_counter() - t0
                self.add(name, timing.seconds)

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {
            name: {"total_s": round(self.totals[name], 4),
                   "calls": self.counts[name],
                   "mean_s": round(self.totals[name] / self.counts[name], 5)}
            for name in sorted(self.totals)
        }
