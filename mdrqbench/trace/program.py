"""The program's own spans (``mdrq.*``) in a profiler trace.

A trace taken with the program's profiler sink on
(``repro.obs.tracing.to_profiler(True)``) holds, on its host planes, one
``mdrq.<name>`` event per span the server opened, with the span's
attributes as event stats: ``flush``, ``plan``, ``execute``, ``sync``
(``bytes``, ``stage``, ``path``), ``backlog_put`` on the admission thread;
``finalize`` and ``sync`` on the finalizer thread. This module adds to
``reduce.py``'s output, which it leaves as it is:

* ``program_spans``: per span name and ``stage`` stat (``""`` where the
  span has none), ``s`` the seconds inside the ``bench.window`` span
  (nested spans count in each of their parents), ``n`` the spans that start
  inside the window, ``bytes`` the summed ``bytes`` stat of those.
* ``idle_in_flush_s``: the device-idle time of the window that falls inside
  ``mdrq.flush`` spans on the admission thread (the thread of
  ``bench.window``), averaged over the devices like ``busy_s``.
* ``idle_gaps``: each label gains `` > `` and the admission thread's
  ``mdrq.*`` span that overlaps the gap most (ties to the shortest span),
  with ``[path]`` where the span has one: ``bench.submit > mdrq.sync[kdtree]``.
  A gap no program span overlaps keeps its label.

On a trace with no ``mdrq.*`` event every key of ``reduce.py`` reads as it
does there, ``program_spans`` is empty and ``idle_in_flush_s`` is 0.
"""
from __future__ import annotations

import json
from pathlib import Path

from mdrqbench.trace import reduce as R

PREFIX = "mdrq."
FLUSH = "mdrq.flush"


def read_program_spans(pd) -> list:
    """``(start_ns, end_ns, name, thread, stats)`` of every ``mdrq.*`` host
    event; ``thread`` names the host line as ``reduce.read_events`` does."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    stats = {k: v for k, v in e.stats}
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, f"{plane.name}/{j}", stats))
    return out


def overlap(xs: list, ys: list) -> float:
    """Total length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def program_label(gap: tuple, prog: list) -> str | None:
    """The program span that overlaps ``gap`` most, the shortest on a tie,
    as ``name[path]``; None where none overlaps it."""
    best = None
    for a, b, name, stats in prog:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov <= 0:
            continue
        key = (ov, a - b)
        if best is None or key > best[0]:
            best = (key, name, stats.get("path"))
    if best is None:
        return None
    return best[1] + (f"[{best[2]}]" if best[2] else "")


def summarize(prog: list, lo: float, hi: float) -> dict:
    """``{name: {stage: {"s", "n", "bytes"}}}`` over the window [lo, hi]."""
    out: dict = {}
    for a, b, name, _th, stats in prog:
        inside = min(b, hi) - max(a, lo)
        starts = lo <= a < hi
        if inside <= 0 and not starts:
            continue
        row = out.setdefault(name, {}).setdefault(
            str(stats.get("stage", "")), {"s": 0.0, "n": 0, "bytes": 0})
        row["s"] += max(inside, 0) * 1e-9
        if starts:
            row["n"] += 1
            row["bytes"] += int(stats.get("bytes", 0))
    return out


def reduce_events(devices: dict, spans: list, prog: list,
                  table: dict) -> dict:
    """``reduce.reduce_events`` with the program's spans added (above)."""
    out = R.reduce_events(devices, spans, table)
    lo, hi, admission = next((a, b, th) for a, b, n, th in spans
                             if n == R.WINDOW_SPAN)
    out["program_spans"] = summarize(prog, lo, hi)
    flush = R.union([(max(a, lo), min(b, hi))
                     for a, b, n, th, _ in prog
                     if n == FLUSH and th == admission and b > lo and a < hi])
    idle_flush, first_idle = [], None
    for name in sorted(devices):
        evs = [(max(a, lo), min(b, hi)) for a, b, _ in devices[name]
               if b > lo and a < hi]
        if not evs:
            continue
        idle = R.gaps(R.union(evs), lo, hi)
        idle_flush.append(overlap(idle, flush))
        if first_idle is None:
            first_idle = idle
    out["idle_in_flush_s"] = sum(idle_flush) / len(idle_flush) * 1e-9
    # the same gaps, in the same order, as reduce_events labelled
    top = sorted(first_idle, key=lambda g: g[0] - g[1])[:R.TOP]
    mine = [(a, b, n, st) for a, b, n, th, st in prog if th == admission]
    for row, gap in zip(out["idle_gaps"], top):
        extra = program_label(gap, mine)
        if extra is not None:
            row[0] = f"{row[0]} > {extra}"
    return out


def reduce_profile(pd, kernels_file: Path = R.KERNELS_FILE) -> dict:
    table = json.loads(Path(kernels_file).read_text())["families"]
    devices, spans = R.read_events(pd)
    return reduce_events(devices, spans, read_program_spans(pd), table)


def reduce_dir(root: Path) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(str(R.find_xplane(root))))


def total(trace: dict, name: str, field: str, stage: str | None = None):
    """A ``program_spans`` field summed over stages (or of one stage); None
    where the trace holds no program spans."""
    ps = trace.get("program_spans") if trace else None
    if not ps:
        return None
    rows = ps.get(PREFIX + name, {})
    if stage is not None:
        return rows[stage][field] if stage in rows else 0
    return sum(r[field] for r in rows.values())


def windows(trace: dict) -> int | None:
    """The ``mdrq.flush`` spans that start in the window; None where
    there are none."""
    n = total(trace, "flush", "n")
    return n or None
