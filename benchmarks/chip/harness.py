"""One run of one benchmark cell: set up, measure, check, print one line.

Everything particular to a cell is data found by name:

* the cell (``workloads`` in ``BENCHMARK.json``) names a configuration, a
  traffic mix and a chip count;
* the configuration is ``configs/<config>.json``: its sizes, the served
  model's file (``served``, a plain-JAX model written for ``gcv.compile``)
  and its plain reference's file (``reference``), the serving settings,
  the work counts and the comparison's limits.  A configuration that
  serves several tasks holds one such entry per task under ``tasks``;
* the mix is ``traffic/<mix>.json``, read by ``loadgen``; its arrival
  process is ``arrivals/<name>.py``, found by the name the mix gives;
* each per-layer metric is ``metrics/<name>.py``, whose ``read(ctx)``
  returns a number or ``None`` when its trace holds nothing to read.

The system under test is ``gcv.serve`` over the configuration's models,
driven through ``submit`` and ``poll``.  Answers are timed by this
module's clock when ``poll`` hands them back: from submission in a closed
loop, from the due time in an open one.  After the window every answer
still owed is waited for, the device's peak memory is read, the program
is freed, and a sample of the window's answers drawn from the seed is
compared with the plain reference at the precision the configuration
states.
"""
from __future__ import annotations

import argparse
import collections
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import time
import types

import numpy as np

import loadgen

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CACHE = ".jax_cache"                     # fixed: the path keys the cache
TRACES = ".bench_trace"
DRAIN_S = 60.0                           # wait for owed answers past close
clock = time.monotonic


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path):
    """Import a file by path (names here hold dots and dashes)."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK")


def tasks_of(cfg: dict) -> dict:
    """``{task: spec}``: the entries under ``tasks``, or the configuration
    itself as its one task."""
    return dict(cfg["tasks"]) if "tasks" in cfg else {cfg["name"]: cfg}


def resolve(bench: dict, workload: str, repo=REPO, here=HERE
            ) -> types.SimpleNamespace:
    """Everything one cell needs, found by the names in ``bench``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    here = pathlib.Path(here)
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(pathlib.Path(repo) / conf["file"])
    tasks = tasks_of(cfg)
    for spec in tasks.values():
        spec["served_file"] = here / spec["served"]
        spec["reference_file"] = here / spec["reference"]
    traffic = load_json(here / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return types.SimpleNamespace(
        name=workload, chips=int(cell["chips"]), cfg=cfg, tasks=tasks,
        serve=cfg["serve"], traffic=traffic,
        arrivals=here / "arrivals" / f"{traffic['arrivals']}.py",
        e2e=e2e, per_layer=per_layer,
        readers={m["name"]: here / "metrics" / f"{m['name']}.py"
                 for m in per_layer})


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (``inf`` for a request never answered)."""
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100 * len(xs)) - 1)]


class Rec:
    """One request as the load generator sees it."""
    __slots__ = ("req", "task", "pool_i", "t_sub", "t_due", "t_ans")

    def __init__(self, req, task, pool_i, t_sub, t_due):
        self.req, self.task, self.pool_i = req, task, pool_i
        self.t_sub, self.t_due, self.t_ans = t_sub, t_due, math.inf


class Answers:
    """What a window keeps of its answers: each request's latency (from
    its due time), answer time and lateness as plain floats, answers per
    task, and per task a reservoir of ``sizes[task]`` answered requests
    drawn from the seed for the comparison.  Keeping every request object
    would make the collector's full passes grow through the window
    (pauses of tens of ms at 30 s)."""

    def __init__(self, sizes: dict, seed: int):
        self.sizes, self.failed = sizes, 0
        self.kept = {t: [] for t in sizes}
        self.n = dict.fromkeys(sizes, 0)
        self.lat, self.t_ans, self.t_due, self.late = [], [], [], []
        self.rng = loadgen.rng(seed, loadgen.SAMPLE_STREAM)

    def add(self, r: Rec) -> None:
        self.lat.append(r.t_ans - r.t_due)
        self.t_ans.append(r.t_ans)
        self.t_due.append(r.t_due)
        self.late.append(r.t_sub - r.t_due)
        if r.req.result is None:
            self.failed += 1
            return
        self.n[r.task] += 1
        kept, size = self.kept[r.task], self.sizes[r.task]
        if len(kept) < size:
            kept.append(r)
        else:
            j = int(self.rng.integers(0, self.n[r.task]))
            if j < size:
                kept[j] = r


class Watch:
    """Diagnostics of the window: compiles (JAX's compile events) and
    garbage-collector pauses, counted only while ``on``."""

    def __init__(self, jax, annotate):
        self.on, self.compiles, self.gc = False, [], []
        self._gc_t0 = None
        self._annotate = annotate
        self._ann = None
        self._monitoring = jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, event, duration, **_):
        if self.on and ("compile" in event or "trace" in event):
            self.compiles.append((event, duration))

    def _gc(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._gc_t0 = clock()
            if self._annotate is not None:
                self._ann = self._annotate("bench.gc")
                self._ann.__enter__()
        elif self._gc_t0 is not None:
            self.gc.append((info["generation"], clock() - self._gc_t0))
            self._gc_t0 = None
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None

    def close(self):
        gc.callbacks.remove(self._gc)
        self._monitoring.unregister_event_duration_listener(self._event)


def annotate_engine(eng, annotate) -> None:
    """Host spans around each call into the engine (trace runs only), so
    that idle device time can be charged to what the host was doing."""
    for attr, span in (("submit", "bench.submit"),
                       ("dispatch", "bench.dispatch"),
                       ("_stack", "bench.stack_inputs"),
                       ("harvest", "bench.harvest")):
        inner = getattr(eng, attr)

        def wrapped(*a, _inner=inner, _span=span, **kw):
            with annotate(_span):
                return _inner(*a, **kw)
        setattr(eng, attr, wrapped)


class Loop:
    """Drives the engine for one window; an arrival process
    (``arrivals/<name>.py``) calls ``closed`` or ``open``.  Each returns
    the requests still owed at the close."""

    def __init__(self, eng, send, t0: float, seconds: float, annotate,
                 done: Answers):
        self.eng, self.send, self.annotate, self.done = (eng, send,
                                                         annotate, done)
        self.t0, self.seconds, self.t_end = t0, seconds, t0 + seconds
        self.paced = False

    def closed(self, clients: int) -> list:
        """Each client sends again as soon as its answer is back."""
        eng, done, t_end = self.eng, self.done, self.t_end
        live = [self.send(None) for _ in range(clients)]
        while clock() < t_end:
            eng.poll()
            now = clock()
            still = []
            for r in live:
                if r.req.done:
                    r.t_ans = now
                    done.add(r)
                    if now < t_end:
                        still.append(self.send(None))
                else:
                    still.append(r)
            live = still
        return live

    def open(self, due_s) -> list:
        """Send each request at its due time (seconds from the window's
        start), whatever the server does."""
        self.paced = True
        eng, done, t_end, annotate = (self.eng, self.done, self.t_end,
                                      self.annotate)
        due = (self.t0 + np.asarray(due_s, float)).tolist()
        live, i, n = [], 0, len(due)
        waiting = None
        while True:
            now = clock()
            if now >= t_end and i >= n:
                break
            sent = 0
            while i < n and due[i] <= now:
                live.append(self.send(due[i]))
                i += 1
                sent += 1
            dispatched, harvested = eng.poll()
            if harvested:
                now = clock()
                still = []
                for r in live:
                    if r.req.done:
                        r.t_ans = now
                        done.add(r)
                    else:
                        still.append(r)
                live = still
            busy = sent or dispatched or harvested
            if annotate is not None:
                if busy and waiting is not None:
                    waiting.__exit__(None, None, None)
                    waiting = None
                elif not busy and waiting is None:
                    waiting = annotate("bench.wait")
                    waiting.__enter__()
            if not busy and i < n and not eng.inflight() \
                    and not eng.pending():
                rest = due[i] - clock()
                if rest > 2e-3:                 # idle: sleep, then spin
                    time.sleep(rest - 1e-3)
        if waiting is not None:
            waiting.__exit__(None, None, None)
        return live


def drain(eng, live: list, done: Answers) -> None:
    """Wait for every answer still owed, up to ``DRAIN_S``."""
    stop = clock() + DRAIN_S
    while live and clock() < stop:
        eng.poll(draining=True)
        now = clock()
        still = []
        for r in live:
            if r.req.done:
                r.t_ans = now
                done.add(r)
            else:
                still.append(r)
        live[:] = still


def per_second_p50(lat, when, t0: float) -> list[float]:
    by_s = collections.defaultdict(list)
    for x, t in zip(lat, when):
        by_s[int(t - t0)].append(x * 1e3)
    return [round(percentile(v, 50), 3) for _, v in sorted(by_s.items())]


def compare(ns, pools: dict, kept: dict, seed: int, jax) -> dict:
    """Per task, the served answers of a seeded sample of requests against
    the plain reference: the median relative L2 error (steady from seed to
    seed; what a loss of precision moves) and the number of answers that
    lie nearer to another sampled input's reference than to their own
    (what a wrong, stale or misplaced answer moves; an exact count).  A
    task with no answer reads infinitely far off.  Keys carry the task's
    name where a configuration serves several."""
    out = {}
    for ti, (task, spec) in enumerate(ns.tasks.items()):
        chk = spec["check"]
        if kept[task]:
            rel, misplaced = reference_errors(
                spec, ns.serve["matmul_precision"], pools[task],
                kept[task], seed, jax, task_i=ti)
            got = {"median_rel_err": float(np.median(rel)),
                   "misplaced": int(misplaced.sum())}
        else:
            got = {"median_rel_err": math.inf, "misplaced": math.inf}
        pre = f"{task}." if len(ns.tasks) > 1 else ""
        out.update({pre + k: {"value": v, "limit": chk[k]}
                    for k, v in got.items()})
    return out


def reference_errors(spec: dict, stated: str, pool: dict, recs: list,
                     seed: int, jax, precision: str | None = None,
                     task_i: int = 0):
    """Per answer of one task: the relative L2 error against the
    reference at the stated precision, and whether the answer lies nearer
    to the reference of another sampled input than to its own (or is not
    a finite array of the reference's shape).  ``precision`` puts the
    reference at another precision in the program's place (the control);
    ``recs`` then need only ``pool_i``."""
    chk = spec["check"]
    ref = load_module(spec["reference_file"])
    params = ref.init_params(seed, **spec["sizes"])
    picks = [recs[i] for i in loadgen.sample(len(recs), chk["sample"],
                                             seed, task_i)]
    uniq = sorted({r.pool_i for r in picks})
    chunk = chk["chunk"]
    want, control = {}, {}
    with jax.default_matmul_precision(stated):
        f = ref.batched(params, precision=stated)
        g = ref.batched(params, precision=precision) if precision else None
        for s in range(0, len(uniq), chunk):
            ids = uniq[s:s + chunk]
            ids = ids + [ids[-1]] * (chunk - len(ids))   # one shape only
            x = {k: v[ids] for k, v in pool.items()}
            want.update(zip(ids, np.asarray(f(**x))))
            if g is not None:
                control.update(zip(ids, np.asarray(g(**x))))
    refs = np.stack([want[i].ravel() for i in uniq]).astype(np.float64)
    row = {i: n for n, i in enumerate(uniq)}
    rel, misplaced, share = [], [], []
    for r in picks:
        got = control[r.pool_i] if g is not None \
            else np.asarray(r.req.result[0])
        w = want[r.pool_i]
        if got.shape != w.shape or not np.isfinite(got).all():
            rel.append(math.inf)
            misplaced.append(True)
            continue
        dist = np.linalg.norm(refs - got.ravel(), axis=1)
        own = dist[row[r.pool_i]]
        dist[row[r.pool_i]] = np.inf
        rel.append(own / float(np.linalg.norm(w)))
        misplaced.append(bool(dist.min() < own))
        share.append(own / (dist.min() + own))
    log(f"compared {len(picks)} answers ({len(uniq)} distinct inputs) "
        f"with the reference; largest error over (error + distance to "
        f"the nearest other reference) {max(share, default=math.inf):.4g}")
    return np.asarray(rel), np.asarray(misplaced)


class Stamps:
    """Set-up time by phase, from the process's start."""

    def __init__(self):
        self.age0, self.t_main = process_age_s(), clock()
        self.last = self.t_main
        self.phases = {"process_start_to_main": self.age0}

    def __call__(self, name: str) -> None:
        now = clock()
        self.phases[name] = now - self.last
        self.last = now

    def since_start(self) -> float:
        return self.age0 + (clock() - self.t_main)


def start_jax(ns, repo, require_tpu: bool):
    """JAX on this cell's chips, with the checkout's compile cache."""
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < ns.chips):
        raise NoChip(f"{ns.name} needs {ns.chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    jax.config.update("jax_compilation_cache_dir",
                      str(pathlib.Path(repo) / CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(pathlib.Path(repo) / "src"))
    return jax, devs[:ns.chips]


def build_server(ns, seed: int, stamp):
    """The configuration's models served by ``gcv.serve``, warmed on the
    buckets this cell's traffic uses, each run once on real inputs."""
    from repro import gcv
    stamp("import_program")
    serve, names = ns.serve, list(ns.tasks)
    pools = {t: loadgen.make_pool(spec["inputs"], ns.traffic["pool"], seed,
                                  ti)
             for ti, (t, spec) in enumerate(ns.tasks.items())}
    stamp("pool")
    models = {t: load_module(spec["served_file"]).build(seed,
                                                        **spec["sizes"])
              for t, spec in ns.tasks.items()}
    eng = gcv.serve(models,
                    max_batch=serve["max_batch_per_chip"] * ns.chips,
                    pipeline_depth=serve["pipeline_depth"],
                    scheduler=serve["scheduler"], kernels=serve["kernels"],
                    devices=ns.chips if ns.chips > 1 else None)
    stamp("build")
    arrivals = load_module(ns.arrivals)
    buckets = eng.buckets()
    if hasattr(arrivals, "warm"):
        buckets = arrivals.warm(ns.traffic, buckets, serve["pipeline_depth"])
    eng.warmup(buckets=buckets)
    stamp("warmup")
    count = 1 << 20
    which = loadgen.task_sequence(ns.traffic, names, count)
    order = loadgen.pool_order(ns.traffic["pool"], count, seed)
    sent = [0]

    def submit(task, i, t_due):
        now = clock()
        req = eng.submit(task, **{k: v[i] for k, v in pools[task].items()})
        return Rec(req, task, i, now, now if t_due is None else t_due)

    def send(t_due):
        n = sent[0] % count
        sent[0] += 1
        return submit(names[which[n]], int(order[n]), t_due)

    for task in names:
        for b in buckets:
            prime = [submit(task, i % ns.traffic["pool"], None)
                     for i in range(b)]
            while not all(r.req.done for r in prime):
                eng.poll(draining=True)
    stamp("first_run")
    return types.SimpleNamespace(
        eng=eng, send=send, pools=pools, seed=seed, arrivals=arrivals,
        kernels={t: eng.models[t].stats()["kernels"] for t in names})


def measure(sv, ns, args, stamps, annotate, watch, obs):
    """Drive the window, close it, and wait for every answer owed."""
    eng = sv.eng
    batches0 = batch_counts(eng)
    setup_s = stamps.since_start()
    done = Answers({t: spec["check"]["sample"]
                    for t, spec in ns.tasks.items()}, sv.seed)
    t0 = clock()
    loop = Loop(eng, sv.send, t0, args.seconds, annotate, done)
    win = annotate("bench.window") if annotate else None
    if win is not None:
        win.__enter__()
    obs_t0 = obs.now()
    watch.on = True
    live = sv.arrivals.drive(loop, ns.traffic)
    watch.on = False
    obs_t1 = obs.now()
    if win is not None:
        win.__exit__(None, None, None)
    t_close = clock()
    if annotate:
        obs.get_tracer().disable()
        import jax
        jax.profiler.stop_trace()
    answered_in_window = len(done.t_ans)
    by_task = dict(done.n)
    drain(eng, live, done)
    tracer = obs.get_tracer()
    m = types.SimpleNamespace(
        done=done, live=live, t_close=t_close, window_s=t_close - t0,
        answered_in_window=answered_in_window, answered_by_task=by_task,
        values=end_to_end(done.lat + [math.inf] * len(live), done.t_ans,
                          t_close, t_close - t0, setup_s),
        spans=[e for e in tracer.events
               if obs_t0 <= tracer.epoch + e["ts"] / 1e6 <= obs_t1])
    batches = {b: n - batches0.get(b, 0)
               for b, n in batch_counts(eng).items()}
    log_window(ns, stamps.phases, t0, done, loop.paced, watch, batches,
               sv.kernels, m.window_s, answered_in_window)
    return m


def per_layer(ns, m, trace_dir, device_kind: str):
    """The cell's per-layer metrics from the trace of its window, and the
    breakdown of where the device time and the idle time went."""
    import tracecalc
    import workcount
    events = tracecalc.read_xplane(tracecalc.find_xplane(trace_dir))
    summary = tracecalc.summarize(events)
    ctx = types.SimpleNamespace(
        cfg=ns.cfg, tasks=ns.tasks, traffic=ns.traffic, chips=ns.chips,
        events=events, summary=summary, spans=m.spans, window_s=m.window_s,
        answered=m.answered_in_window,
        answered_by_task=m.answered_by_task,
        peaks=workcount.peaks(device_kind))
    metrics = {}
    for spec in ns.per_layer:
        v = load_module(ns.readers[spec["name"]]).read(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    log(f"trace: busy {summary['busy_s']:.4f} s of "
        f"{summary['window_s']:.4f} s over {summary['devices']} "
        f"device(s); top ops {summary['device_ops'][:5]}; idle by "
        f"host span {summary['idle_gaps']}")
    log(f"trace: longest idle gaps (start s, seconds, by host span) "
        f"{summary['longest_gaps']}; longest ops {summary['longest_ops']}")
    return metrics, summary


def run(args, bench: dict, repo=REPO, here=HERE, require_tpu=True,
        engine_hook=None) -> dict:
    """One run of one cell; returns the result line's object."""
    stamps = Stamps()
    ns = resolve(bench, args.workload, repo, here)
    trace_dir = pathlib.Path(repo) / TRACES / ns.name
    jax, devs = start_jax(ns, repo, require_tpu)
    stamps("jax_init")
    from repro import obs
    seed = args.seed % 2**63
    annotate = jax.profiler.TraceAnnotation if args.trace else None
    watch = Watch(jax, annotate)
    precision = ns.serve["matmul_precision"]
    try:
        with jax.default_matmul_precision(precision):
            sv = build_server(ns, seed, stamps)
            if engine_hook is not None:
                engine_hook(sv.eng)
            if args.trace:
                annotate_engine(sv.eng, annotate)
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(str(trace_dir),
                                         profiler_options=opts)
                obs.get_tracer().clear()
                obs.get_tracer().enable()
                stamps("trace_start")
            m = measure(sv, ns, args, stamps, annotate, watch, obs)
            memory_peak = max((d.memory_stats() or {}).get(
                "peak_bytes_in_use", 0) for d in devs)
            pools = sv.pools
            del sv                            # free the program's state
            gc.collect()
    finally:
        watch.close()

    failed = m.done.failed + len(m.live)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    out = {}
    if args.trace:
        metrics, summary = per_layer(ns, m, trace_dir, devs[0].device_kind)
        device.update(busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    else:
        metrics = {spec["name"]: {"value": m.values[spec["name"]],
                                  "unit": spec["unit"]} for spec in ns.e2e}
    checks = compare(ns, pools, m.done.kept, seed, jax)
    checks["failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return {"correct": correct,
            "attempted": len(m.done.lat) + len(m.live),
            "failed": failed, "metrics": metrics, "device": device, **out,
            "checks": checks}


def end_to_end(lat: list, t_ans: list, t_close: float, window_s: float,
               setup_s: float) -> dict:
    """The end-to-end numbers of a window.  The rate counts every answer
    back by the close over the whole window; the percentiles are over
    every request of the window, each timed from its due time (its
    submission in a closed loop) to its answer, an unanswered one as
    infinitely late."""
    return {"setup_s": setup_s,
            "throughput_rps": sum(1 for t in t_ans if t <= t_close)
            / window_s,
            "p50_ms": percentile(lat, 50) * 1e3,
            "p95_ms": percentile(lat, 95) * 1e3}


def batch_counts(eng) -> dict[int, int]:
    """Batches dispatched per bucket, from the engine's own histograms."""
    out = {}
    for name, snap in eng.metrics.snapshot().items():
        if name.startswith("service_ms.") and isinstance(snap, dict):
            b = int(name.rsplit(".b", 1)[1])
            out[b] = out.get(b, 0) + snap["count"]
    return out


def log_window(ns, stamps, t0, done, paced, watch, batches, kernels,
               window_s, answered_in_window) -> None:
    log(f"{ns.name}: set-up " + ", ".join(
        f"{k} {v:.3f} s" for k, v in stamps.items()))
    log(f"{ns.name}: kernels {kernels}")
    if paced:
        late = sorted(x * 1e3 for x in done.late)
        if late:
            log(f"generator late ms: p50 {percentile(late, 50):.3f} "
                f"p99 {percentile(late, 99):.3f} max {late[-1]:.3f}")
    log(f"window {window_s:.3f} s, answered in window "
        f"{answered_in_window}, compiles in window {len(watch.compiles)} "
        f"{watch.compiles[:3]}")
    pauses = [p for _, p in watch.gc]
    log(f"gc pauses {len(pauses)}, max "
        f"{max(pauses, default=0) * 1e3:.3f} ms, total "
        f"{sum(pauses) * 1e3:.3f} ms")
    n = answered_in_window
    when = done.t_due if paced else done.t_ans
    log(f"p50 ms per second: "
        f"{per_second_p50(done.lat[:n], when[:n], t0)}")
    log(f"batches by bucket: {dict(sorted(batches.items()))}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(REPO / "BENCHMARK.json")
    try:
        out = run(args, bench)
    except NoChip as e:
        log(f"error: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0
