"""Profiler trace (``.xplane.pb``) -> device busy time, kernel time, idle gaps.

* The window is the ``bench.window`` span that the harness writes around
  the measured loop (``jax.profiler.TraceAnnotation``), on the host plane.
* Device operations are the events of each TPU plane's ``XLA Ops`` line.
  Busy time is the union of their intervals inside the window, averaged
  over the devices that ran any; the idle share is 1 - busy / window.
* Kernel time is the sum of the durations of the device operations whose
  name matches a pattern of ``kernels.json``, by kernel family. On a TPU an
  operation's name is its HLO instruction; a Pallas kernel is a
  ``custom-call`` named after the jitted op that launches it.
* Idle gaps are the intervals of the window in which no device operation
  ran, each labelled with the harness span (``bench.*``) of the admission
  thread that covers most of it: what the load generator was doing while
  the device waited (``none``: between the harness's calls).
"""
from __future__ import annotations

import json
import re
from pathlib import Path

KERNELS_FILE = Path(__file__).with_name("kernels.json")
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(root: Path) -> Path:
    files = sorted(Path(root).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The intervals of [lo, hi] that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def label(gap: tuple[float, float], spans: list[tuple[float, float, str]]):
    """The name of the span that overlaps ``gap`` most, or ``"none"``."""
    best, name = 0.0, "none"
    for a, b, n in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def short_name(name: str) -> str:
    """``%op.1 = s8[64,10000384]{layout} custom-call(...)`` -> ``op.1
    s8[64,10000384]``: the instruction and its result shape."""
    m = re.match(r"^%?(\S+) = (\(|[a-z0-9]+\[[0-9,]*\])", name)
    if not m:
        return name[:120]
    shape = "(tuple)" if m.group(2) == "(" else m.group(2)
    return f"{m.group(1)} {shape}"


def kernel_family(name: str, table: dict) -> str | None:
    for family, patterns in table.items():
        if any(re.search(p, name) for p in patterns):
            return family
    return None


def read_events(pd):
    """(device op events per device, bench spans) of a ``ProfileData``.

    Device events are (start_ns, end_ns, name); spans are (start_ns,
    end_ns, name, thread), the thread being the host line they are on.
    """
    devices, spans = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for j, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name, f"{plane.name}/{j}"))
    return devices, spans


def reduce_events(devices: dict, spans: list, table: dict) -> dict:
    windows = [(a, b, th) for a, b, n, th in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    lo, hi, admission = windows[0]
    window_ns = hi - lo
    busy_ns, ops, kernels, n_kernels = [], {}, {}, {}
    first_busy = None
    for name in sorted(devices):
        evs = [(max(a, lo), min(b, hi), n) for a, b, n in devices[name]
               if b > lo and a < hi]
        if not evs:
            continue
        merged = union([(a, b) for a, b, _ in evs])
        busy_ns.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged
        for a, b, n in evs:
            op = short_name(n)
            ops[op] = ops.get(op, 0.0) + (b - a)
            fam = kernel_family(n, table)
            if fam is not None:
                kernels[fam] = kernels.get(fam, 0.0) + (b - a)
                n_kernels[fam] = n_kernels.get(fam, 0) + 1
    if not busy_ns:
        raise ValueError("no device operation ran in the traced window")
    # what the admission thread (the one that ran the window) was doing
    labelled = [(a, b, n) for a, b, n, th in spans
                if th == admission and n != WINDOW_SPAN]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "n_devices": len(busy_ns),
        "device_ops": [[n, v * 1e-9] for n, v in top_ops],
        "idle_gaps": [[label(g, labelled), (g[1] - g[0]) * 1e-9]
                      for g in idle[:TOP]],
        "kernel_s": {k: v * 1e-9 for k, v in kernels.items()},
        "kernel_events": n_kernels,
    }


def reduce_profile(pd, kernels_file: Path = KERNELS_FILE) -> dict:
    table = json.loads(Path(kernels_file).read_text())["families"]
    devices, spans = read_events(pd)
    return reduce_events(devices, spans, table)


def reduce_file(path: Path) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(path)))


def reduce_dir(root: Path) -> dict:
    return reduce_file(find_xplane(root))
