"""Reduction from a profiler trace to the benchmark's device numbers.

One profiler trace (the ``.xplane.pb`` that ``jax.profiler`` writes) is
read once into plain tuples, ``(name, start_ns, end_ns)``, split into
device operations (the ``XLA Ops`` line of every ``/device:TPU:<n>``
plane) and host annotations (the benchmark's ``bench.*`` spans on the
host plane, on the same clock).  Everything else here works on those
tuples, so a recorded trace reduces the same way on any machine:

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the measured window (the ``bench.window`` span);
* idle gaps: the rest of the window, each piece charged to the innermost
  host annotation open at that moment (``outside_bench_calls`` where
  none is);
* kernel time: the summed device durations of the operations whose name
  contains a kernel's tag;
* roofline share: the least time the chip could take for the work,
  the larger of operations over peak rate and bytes over peak bandwidth,
  over the measured time.
"""
from __future__ import annotations

import bisect
import collections
import json
import pathlib

WINDOW = "bench.window"
OUTSIDE = "outside_bench_calls"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_xplane(log_dir) -> pathlib.Path:
    """The one ``.xplane.pb`` under a profiler log directory."""
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(found)}")
    return found[0]


def read_xplane(path) -> dict:
    """``{"devices": {plane: [(name, t0, t1), ...]}, "host": [...]}`` from
    one trace file, times in nanoseconds on the profiler's clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                               for ev in line.events)
            devices[plane.name] = sorted(ops, key=lambda e: e[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events
                            if ev.name.startswith("bench."))
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def save_events(events: dict, path) -> None:
    pathlib.Path(path).write_text(json.dumps(events))


def load_events(path) -> dict:
    raw = json.loads(pathlib.Path(path).read_text())
    return {"devices": {k: [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def window(events: dict) -> tuple[float, float]:
    """The measured window, from the benchmark's ``bench.window`` span."""
    spans = [(a, b) for name, a, b in events["host"] if name == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(spans)}")
    return spans[0]


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for _, a, b in sorted(intervals, key=lambda e: e[1]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(ops, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(ops, lo, hi))


def gaps(ops, lo: float, hi: float) -> list[tuple[float, float]]:
    """The pieces of ``[lo, hi]`` in which no operation ran."""
    out, t = [], lo
    for a, b in union(ops, lo, hi):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def charge_gaps(gap_list, host) -> dict[str, float]:
    """Nanoseconds of idle time per host annotation: each instant of a gap
    goes to the innermost annotation open then (the latest-started, and
    of those the shortest, that contains it), or to
    ``outside_bench_calls``."""
    spans = [(a, b, name) for name, a, b in host if name != WINDOW]
    spans.sort()
    starts = [s[0] for s in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    out: dict[str, float] = collections.defaultdict(float)
    for g0, g1 in gap_list:
        first = bisect.bisect_left(starts, g0 - longest)
        last = bisect.bisect_right(starts, g1)
        live = [s for s in spans[first:last] if s[1] > g0 and s[0] < g1]
        cuts = sorted({g0, g1, *(min(max(t, g0), g1)
                                 for a, b, _ in live for t in (a, b))})
        for c0, c1 in zip(cuts, cuts[1:]):
            mid = (c0 + c1) / 2
            inner = [s for s in live if s[0] <= mid < s[1]]
            name = max(inner, key=lambda s: (s[0], -s[1]))[2] \
                if inner else OUTSIDE
            out[name] += c1 - c0
    return dict(out)


def short_name(name: str) -> str:
    """An operation's name and result shape, without layouts or operands
    (``%fusion.3 = f32[8,1024,256]``)."""
    cut = min((i for i in (name.find("{"), name.find("(")) if i > 0),
              default=len(name))
    return name[:cut].strip()


def op_totals(ops, lo: float, hi: float) -> dict[str, float]:
    """Nanoseconds per operation, over the operations that started in the
    window."""
    out: dict[str, float] = collections.defaultdict(float)
    for name, a, b in ops:
        if lo <= a < hi:
            out[short_name(name)] += b - a
    return dict(out)


def kernel_ns(ops, tag: str, lo: float, hi: float) -> tuple[float, int]:
    """Summed duration and count of the operations whose own name (before
    `` = ``, not their operands) contains ``tag`` (case-insensitive) and
    that started in the window."""
    tag = tag.lower()
    hits = [b - a for name, a, b in ops
            if tag in name.split(" = ", 1)[0].lower() and lo <= a < hi]
    return float(sum(hits)), len(hits)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak_flops: float, peak_bytes: float) -> tuple[float,
                                                                  str]:
    """Percent of the roofline reached, and which bound sets it."""
    t_ops, t_bytes = ops / peak_flops, nbytes / peak_bytes
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound


def summarize(events: dict, top: int = 10) -> dict:
    """Busy and window seconds averaged over the devices, and the
    breakdown: the device operations that took most time and the longest
    idle time by host annotation, each averaged over the devices."""
    lo, hi = window(events)
    devs = sorted(events["devices"])
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy, ops_tot, idle = 0.0, collections.Counter(), collections.Counter()
    for d in devs:
        ops = events["devices"][d]
        busy += busy_ns(ops, lo, hi)
        ops_tot.update(op_totals(ops, lo, hi))
        idle.update(charge_gaps(gaps(ops, lo, hi), events["host"]))
    n = len(devs)
    first = events["devices"][devs[0]]
    longest = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:5]
    longest_ops = sorted(((b - a, short_name(nm), a) for nm, a, b in first
                          if lo <= a < hi), reverse=True)[:5]
    return {
        "longest_gaps": [[(a - lo) / 1e9, (b - a) / 1e9,
                          charge_gaps([(a, b)], events["host"])]
                         for a, b in longest],
        "longest_ops": [[nm, (a - lo) / 1e9, d / 1e9]
                        for d, nm, a in longest_ops],
        "devices": n,
        "busy_s": busy / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[k, v / n / 1e9]
                       for k, v in ops_tot.most_common(top)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in idle.most_common(top)],
    }
