"""The one reduction from a profiler trace (`.xplane.pb`) to device
numbers: busy and idle seconds, device time per XLA program, the device
operations that took most time, and the longest idle gaps named by what
the host was doing. Reads the file with `jax.profiler.ProfileData` and
nothing else. Layout as seen on the v5e: device planes are
`/device:TPU:n`; their "XLA Modules" line has one event per program run,
named `jit_<fn>(<fingerprint>)`; "XLA Ops" has one event per operation.
Host spans are the benchmark's own `TraceAnnotation`s (names starting
`bench:`), found on the `/host:CPU` plane.

A trace without a device plane raises: device time comes only from a
trace taken on the chip.
"""

import glob
import os

MODULES, OPS = "XLA Modules", "XLA Ops"
HOST_PREFIX = "bench:"


def start(trace_dir):
    """Start the profiler into an emptied `trace_dir`, Python's own
    tracer off (it slows the host and the spans the reduction reads are
    `TraceAnnotation`s). The caller stops it: `jax.profiler.stop_trace()`."""
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def newest_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text):
    """`%fusion.85 = (...) fusion(...)`, the HLO text the trace prints for
    an operation, cut to `fusion.85`."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _plane_reduce(plane):
    ops, modules = [], {}
    for line in plane.lines:
        if line.name == OPS:
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                    op_name(ev.name)) for ev in line.events]
        elif line.name == MODULES:
            for ev in line.events:
                modules.setdefault(ev.name.split("(")[0], []).append(
                    (ev.start_ns, ev.duration_ns))
    return ops, modules


def reduce_xplane(path, device_prefix="/device:TPU", top=10):
    """-> dict(busy_s, window_s, devices, programs {name: [ms, ...]},
    device_ops [[name, s], ...], idle_gaps [[name, s], ...]).

    busy_s and window_s are averaged over the device planes that ran
    anything; the window of a plane runs from its first operation's start
    to its last one's end (a steady slice: the profiler's own start and
    stop lie outside it). programs, device_ops and idle_gaps are those of
    the busiest plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if p.name.startswith(device_prefix)]
    if not planes:
        raise RuntimeError(
            f"{path} has no {device_prefix} plane (planes: "
            f"{[p.name for p in data.planes]}): device time comes only "
            "from a trace taken on the chip")
    host = []
    for p in data.planes:
        if p.name.startswith("/host:"):
            for line in p.lines:
                host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in line.events
                         if ev.name.startswith(HOST_PREFIX)]
    per_plane = []
    for p in planes:
        ops, modules = _plane_reduce(p)
        if not ops:
            continue
        busy = _union((a, b) for a, b, _ in ops)
        per_plane.append({
            "busy_ns": sum(b - a for a, b in busy),
            "window_ns": busy[-1][1] - busy[0][0],
            "busy": busy, "ops": ops, "modules": modules})
    if not per_plane:
        raise RuntimeError(f"{path}: no operation ran on any device plane")
    best = max(per_plane, key=lambda r: r["busy_ns"])
    by_op = {}
    for a, b, name in best["ops"]:
        by_op[name] = by_op.get(name, 0.0) + (b - a) / 1e9
    gaps = {}
    for (_, end), (start, _) in zip(best["busy"], best["busy"][1:]):
        name = _host_during(host, end, start)
        gaps[name] = gaps.get(name, 0.0) + (start - end) / 1e9

    def ranked(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:top]]

    n = len(per_plane)
    return {
        "busy_s": sum(r["busy_ns"] for r in per_plane) / n / 1e9,
        "window_s": sum(r["window_ns"] for r in per_plane) / n / 1e9,
        "devices": n,
        "programs": {k: [d / 1e6 for _, d in v]
                     for k, v in best["modules"].items()},
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(gaps),
    }


def _host_during(host, a, b):
    """Name of the benchmark's host span that covers most of (a, b)."""
    name, most = "host:unattributed", 0.0
    for s, e, n in host:
        cover = min(e, b) - max(s, a)
        if cover > most:
            name, most = n, cover
    return name
