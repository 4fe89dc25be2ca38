"""The program's own serving spans on the profiler trace, and the device
idle time each holds.

``gcv.serve`` opens live spans named ``serve.*`` (``serve.schedule``,
``serve.stack``, ``serve.transfer``, ``serve.launch`` when it dispatches
a batch; ``serve.wait``, ``serve.copy``, ``serve.unpack`` when it
harvests one).  While the program's tracer is on, each is also a profiler
annotation, so it lands on the trace's host plane, on the clock of the
device's operations.  ``tracecalc.read_xplane`` keeps the benchmark's
``bench.*`` spans only; ``program(ctx)`` reads the ``serve.*`` ones from
the same trace, as ``(name, start_ns, end_ns)`` tuples.  A program older
than these spans gives none, and the readers built on them then read
nothing.
"""
from __future__ import annotations

import bisect
import collections
import importlib
import pathlib

import harness
import tracecalc

PREFIX = "serve."
ROOT = pathlib.Path(__file__).resolve().parents[2]


def read_program(path) -> tuple[list, tuple | None]:
    """The ``serve.*`` host-plane events of one trace file, sorted by
    start, and the file's ``bench.window`` interval (``None`` without
    one)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, win = [], None
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
                elif ev.name == tracecalc.WINDOW:
                    win = (ev.start_ns, ev.start_ns + ev.duration_ns)
    spans.sort(key=lambda e: e[1])
    return spans, win


def program(ctx) -> list:
    """The ``serve.*`` spans of the trace that ``ctx.events`` was read
    from: ``ctx.events["program"]`` where it is there, else those of the
    newest trace under the checkout's trace directory whose
    ``bench.window`` is the window of ``ctx.events`` (kept in
    ``ctx.events["program"]`` for the next reader); ``[]`` where none
    matches."""
    if "program" not in ctx.events:
        found = sorted((ROOT / harness.TRACES).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime_ns)
        spans, win = read_program(found[-1]) if found else ([], None)
        ctx.events["program"] = \
            spans if win == tracecalc.window(ctx.events) else []
    return ctx.events["program"]


def _groups(spans) -> list:
    """Maximal runs of overlapping spans (sorted by start), each as
    ``[start, end, members]``."""
    out: list = []
    for e in spans:
        if out and e[1] < out[-1][1]:
            out[-1][1] = max(out[-1][1], e[2])
            out[-1][2].append(e)
        else:
            out.append([e[1], e[2], [e]])
    return out


def idle_by_span(ctx) -> dict[str, float] | None:
    """Nanoseconds of device idle time in the window per ``serve.*``
    span name, averaged over the devices; ``None`` without program spans.

    Each instant of an idle gap goes to the innermost span open then, as
    ``tracecalc.charge_gaps`` decides over the program's spans and the
    benchmark's ``bench.*`` ones together (a ``bench.gc`` inside a
    ``serve.*`` span keeps its time), and is counted here where that span
    is a ``serve.*`` one.  The charge runs per run of overlapping program
    spans, with the ``bench.*`` spans that start inside it: one that
    starts earlier is never the innermost there, since a program span
    that started later is open at every instant of the run."""
    if hasattr(ctx, "program_idle"):
        return ctx.program_idle
    spans = program(ctx)
    if not spans:
        ctx.program_idle = None
        return None
    lo, hi = tracecalc.window(ctx.events)
    host = sorted((e for e in ctx.events["host"]
                   if e[0] != tracecalc.WINDOW), key=lambda e: e[1])
    host_starts = [e[1] for e in host]
    groups = _groups(sorted(spans, key=lambda e: e[1]))
    out: dict[str, float] = collections.defaultdict(float)
    devices = ctx.events["devices"]
    for ops in devices.values():
        gaps = tracecalc.gaps(ops, lo, hi)
        gap_starts = [g0 for g0, _ in gaps]
        for a, b, members in groups:
            j = max(0, bisect.bisect_right(gap_starts, a) - 1)
            pieces = []
            while j < len(gaps) and gaps[j][0] < b:
                g0, g1 = gaps[j]
                if g1 > a:
                    pieces.append((max(g0, a), min(g1, b)))
                j += 1
            if not pieces:
                continue
            inside = host[bisect.bisect_left(host_starts, a):
                          bisect.bisect_left(host_starts, b)]
            for name, ns in tracecalc.charge_gaps(pieces,
                                                  members + inside).items():
                if name.startswith(PREFIX):
                    out[name] += ns
    ctx.program_idle = {k: v / len(devices) for k, v in out.items()}
    counts = collections.Counter(name for name, _, _ in spans)
    counts.update(s["name"] for s in ctx.spans
                  if s["args"].get("device", 0) == 0)
    idle_s = {k: v / 1e9 for k, v in sorted(ctx.program_idle.items())}
    harness.log(f"trace: program spans and events "
                f"{dict(sorted(counts.items()))}; idle s by program span "
                f"{idle_s}")
    return ctx.program_idle


def idle_ms_per(ctx, names, event: str) -> float | None:
    """Idle device milliseconds charged to the spans ``names`` per
    ``event`` of the program (device 0) in the window."""
    idle = idle_by_span(ctx)
    n = sum(1 for s in ctx.spans
            if s["name"] == event and s["args"].get("device", 0) == 0)
    if idle is None or not n:
        return None
    return sum(idle.get(k, 0.0) for k in names) / n / 1e6


def counts_stalls() -> bool:
    """Whether the program under test counts runtime stalls."""
    return hasattr(importlib.import_module("repro.serve.gnncv"), "STALL_MS")


def stall_ms_per_s(ctx) -> float | None:
    """Milliseconds of the window's ``serve.stall`` events per second of
    window (0.0 without a stall); ``None`` for a program that counts no
    stalls."""
    if not counts_stalls():
        return None
    stalls = [(s["args"]["call"], s["dur"] / 1e3) for s in ctx.spans
              if s["name"] == "serve.stall"]
    harness.log(f"trace: serve.stall (call, ms) "
                f"{[(c, round(ms, 3)) for c, ms in stalls]}")
    return sum(ms for _, ms in stalls) / ctx.window_s
