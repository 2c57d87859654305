"""One run of one benchmark cell, driven by data.

``BENCHMARK.json`` names the cell; the cell names a configuration file
(``configs/<config>.json``) and a traffic mix (``workloads/<cell>.json``).
The configuration names its deployment code (``deploy/<deployment>.py``)
and its data generator (``data/<generator>.py``); the traffic mix names
its loop (``loops/<loop>.py``) and its query templates
(``queries/<template>.py``); every metric is read by ``metrics/<name>.py``.
Adding a cell or a metric adds files and entries and edits none.

A run: set-up (data from the seed, loading, one warm-up of every query
the window will send), the measured window, then — once the window has
closed, the device's memory peak has been read and the system's state is
dropped — the plain reference over the same generated columns, and the
comparison of every answer the window produced against it.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_trace"


class NoDevice(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict           # the cell's entry in BENCHMARK.json
    config: dict          # configs/<config>.json
    workload: dict        # workloads/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, bench_path: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    bench = read_json(bench_path)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in {bench_path}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, entry, read_json(ROOT / conf["file"]),
                read_json(HERE / "workloads" / f"{entry['traffic']}.json"),
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; raises :class:`NoDevice` unless
    they are TPUs and at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoDevice(f"the cell asks for {chips} chips, JAX finds "
                       f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts jit specialisations traced and programs compiled by XLA
    while registered (a program found in the persistent cache is traced,
    not compiled)."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0}

    def _listen(self, event, duration, **kw):
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


class GcTimer:
    """Counts the interpreter's garbage collections while registered and
    the seconds they took: ``count[0]``/``seconds[0]`` over every
    generation, ``[2]`` the full ones."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t0 = 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        for i in {0, info["generation"]}:
            self.count[i] += 1
            self.seconds[i] += dt

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)
        return False


@dataclasses.dataclass
class Spec:
    """One query the cell can send: a template with its parameters, on one
    tenant's data."""
    tenant: int
    template: str
    index: int            # which of the template's parameter sets
    params: dict
    dataset: Any
    rows: int


@dataclasses.dataclass
class Query:
    """One query the window sent (``sent``/``done`` on the host's
    monotonic clock, ns)."""
    spec: Spec
    due: int
    sent: int
    done: int
    ok: bool
    answer: Optional[Dict[str, np.ndarray]] = None
    error: Optional[str] = None
    trace: Any = None
    groups: Optional[list] = None  # the AGG's output, where tapped
    work: Optional[dict] = None   # the template's declared work


class Context:
    """What a loop needs: the schedule, the window's length, and the call
    that sends one query."""

    def __init__(self, workload: dict, schedule: list, seconds: float,
                 clients: dict, trace: bool, tap: Optional[list] = None):
        self.workload = workload
        self.schedule = schedule
        self.seconds = seconds
        self.window = (0, 0)
        self._clients = clients
        self._trace = trace
        self._tap = tap

    def execute(self, spec: Spec, due: int) -> Query:
        """Send one query and wait for its answer. Queries are sent one at
        a time: a query's trace and tapped AGG output are the client's
        latest."""
        if self._tap is not None:
            self._tap.clear()
        sent = time.monotonic_ns()
        try:
            answer = spec.dataset.collect()
        except Exception as e:  # a failed query is counted, not fatal
            return Query(spec, due, sent, time.monotonic_ns(), False,
                         error=f"{type(e).__name__}: {e}")
        done = time.monotonic_ns()
        q = Query(spec, due, sent, done, True, answer=answer)
        if self._trace:
            q.trace = self._clients[spec.tenant].last_trace
        if self._tap is not None:
            q.groups = list(self._tap)
        return q


def draw_params(entry: dict, rng: np.random.Generator) -> List[dict]:
    """A template's parameter sets: listed (``fixed``) or drawn from
    inclusive integer ranges (``draw``, ``count`` distinct sets)."""
    p = entry["params"]
    if "fixed" in p:
        return [dict(x) for x in p["fixed"]]
    names = sorted(p["draw"])
    space = int(np.prod([p["draw"][k][1] - p["draw"][k][0] + 1
                         for k in names]))
    if p["count"] > space:
        raise ValueError(f"{entry['template']}: {p['count']} distinct "
                         f"parameter sets asked of {space}")
    out: List[dict] = []
    while len(out) < p["count"]:
        cand = {k: int(rng.integers(p["draw"][k][0], p["draw"][k][1] + 1))
                for k in names}
        if cand not in out:
            out.append(cand)
    return out


def _schema(dtype: np.dtype, name: str):
    from repro.objectmodel.schema import Field, record
    return record(name, {f: Field(dtype.fields[f][0]) for f in dtype.names})


class Bench:
    """One cell set up from a seed: its data, the deployed system, every
    query the window may send (each warmed up once), and the windows and
    checks run against them."""

    def __init__(self, cell: Cell, seed: int, trace: bool,
                 require_tpu: bool = True, log=sys.stderr):
        self.cell, self.trace = cell, trace
        self.require_tpu, self.log = require_tpu, log
        self.chips = cell.entry["chips"]
        self.device = (device_info(self.chips) if require_tpu else
                       {"platform": "cpu", "kind": "cpu", "count": 1})
        conf, wl = cell.config, cell.workload
        self.gen = load_module(HERE / "data" / f"{conf['generator']}.py",
                               "bench_data")
        deploy = load_module(HERE / "deploy" / f"{conf['deployment']}.py",
                             "bench_deploy")
        self.loop = load_module(HERE / "loops" / f"{wl['loop']}.py",
                                "bench_loop")
        self.templates = {m["template"]: load_module(
            HERE / "queries" / f"{m['template']}.py",
            f"bench_q_{m['template']}") for m in wl["mix"]}

        self.tables = [self.gen.generate(conf["scale_factor"],
                                         np.random.SeedSequence([seed, t]))
                       for t in range(conf["tenants"])]
        schema = _schema(self.tables[0].dtype, conf["schema"])
        self.dep = deploy.Deployment(conf, schema, trace)
        self.dep.load(self.tables)
        self.rng = np.random.default_rng(
            np.random.SeedSequence([seed, 1 << 20]))
        self.params = {m["template"]: draw_params(m, self.rng)
                       for m in wl["mix"]}
        self.specs = []
        for t in range(conf["tenants"]):
            session, set_name = self.dep.client(t)
            for name, plist in self.params.items():
                for i, p in enumerate(plist):
                    self.specs.append(Spec(
                        t, name, i, p, self.templates[name].build(
                            session, set_name, schema, p),
                        len(self.tables[t])))
        for s in self.specs:
            s.dataset.collect()
        self.clients = {t: self.dep.client(t)[0]
                        for t in range(conf["tenants"])}
        # a template that declares its whole groups is checked on every
        # group its AGG produced, not only on those its answer keeps
        self.tap = None
        if any(hasattr(m, "groups") for m in self.templates.values()):
            self.tap = []
            self.dep.tap_aggregates(self.tap)

    def window(self, seconds: float):
        """One measured window; returns its queries and what was read
        while it ran."""
        from repro.obs.metrics import METRICS
        wl = self.cell.workload
        sched = self.loop.schedule(wl, self.specs, self.rng, seconds)
        ctx = Context(wl, sched, seconds, self.clients, self.trace,
                      self.tap)
        before = METRICS.snapshot()["counters"]
        prof = (_Profiler(TRACE_DIR) if self.trace and self.require_tpu
                else None)
        with CompileCounter() as cc, GcTimer() as gct:
            if prof is not None:
                prof.start()
            queries = self.loop.run(ctx)
            if prof is not None:
                prof.stop(ctx.window)
        after = METRICS.snapshot()["counters"]
        counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
        print(f"window: {len(queries)} queries, "
              f"{(ctx.window[1] - ctx.window[0]) / 1e9:.3f} s, jit traces "
              f"{cc.counts['traces']}, XLA compiles {cc.counts['compiles']}",
              file=self.log)
        print("garbage collections: %d, %.3f s (full: %d, %.3f s)" % (
            gct.count[0], gct.seconds[0], gct.count[2], gct.seconds[2]),
            file=self.log)
        if len(queries) <= 20:
            print("query s: " + " ".join(
                "%.3f" % ((q.done - q.sent) / 1e9) for q in queries),
                file=self.log)
        lag = np.array([(q.sent - q.due) / 1e6 for q in queries])
        print("generator lag ms: p50 %.3f p95 %.3f max %.3f" % (
            np.percentile(lag, 50), np.percentile(lag, 95), lag.max()),
            file=self.log)
        return queries, ctx.window, counters, prof

    def close(self) -> None:
        """Drop the system's state: the deployment and every query."""
        self.dep.close()
        self.dep = self.clients = self.tap = None
        for s in self.specs:
            s.dataset = None
        gc.collect()

    def verify(self, queries: List[Query]):
        """The reference over the generated columns, and every answer
        compared with it. Call after :meth:`close`. Returns the readings,
        the limits, the failed queries, and whether the run is correct."""
        from check import combine, compare, tapped_groups, within
        refs, work, groups = {}, {}, {}
        reads = sorted({c for m in self.templates.values() for c in m.READS})
        for t, rec in enumerate(self.tables):
            c = self.gen.columns(rec, reads)
            for name, plist in self.params.items():
                mod = self.templates[name]
                for i, r in enumerate(mod.references(c, plist,
                                                     np.float64)):
                    refs[(t, name, i)] = r
                    work[(t, name, i)] = mod.work(c, plist[i])
                if hasattr(mod, "groups"):
                    groups[(t, name)] = mod.groups(c, np.float64)
        self.tables = None
        readings = []
        for q in queries:
            key = (q.spec.tenant, q.spec.template, q.spec.index)
            q.work = work[key]
            if not q.ok:
                continue
            mod = self.templates[q.spec.template]
            readings.append(compare(q.answer, refs[key], mod.KEYS))
            if (q.spec.tenant, q.spec.template) in groups:
                readings.append(compare(
                    tapped_groups(q.groups),
                    groups[(q.spec.tenant, q.spec.template)], mod.KEYS))
        read = combine(readings)
        limits = self.cell.workload["limits"]
        errors = [q for q in queries if not q.ok]
        for q in errors[:3]:
            print(f"query failed: {q.spec.template} {q.spec.params}: "
                  f"{q.error}", file=self.log)
        n_answers = sum(q.ok for q in queries)
        correct = within(read, limits) and not errors and bool(readings)
        return read, limits, errors, n_answers, correct


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: Optional[float] = None, require_tpu: bool = True,
        log=sys.stderr) -> dict:
    """One run; returns the result object (without printing it)."""
    from check import READINGS
    t_start = time.monotonic() if t_start is None else t_start
    bench = Bench(cell, seed, trace, require_tpu, log)
    setup_s = time.monotonic() - t_start
    print(f"setup: {setup_s:.3f} s, {len(bench.specs)} warm-up queries, "
          f"{sum(len(t) for t in bench.tables)} rows", file=log)
    queries, window, counters, prof = bench.window(seconds)
    peak = memory_peak_bytes(bench.chips) if require_tpu else 0
    if prof is not None:
        from trace_reduce import spans_from_traces
        prof.reduce(spans_from_traces(
            [q.trace for q in queries if q.trace is not None]))
    bench.close()
    read, limits, errors, n_read, correct = bench.verify(queries)

    device = bench.device
    peaks = read_json(HERE / "peaks.json")
    run_rec = Run(cell=cell, seconds=seconds, setup_s=setup_s,
                  queries=queries, window=window, counters=counters,
                  device=prof.reduction if prof is not None else None,
                  peak=peaks.get(device["kind"]) if require_tpu else None)
    if require_tpu and run_rec.peak is None:
        raise NoDevice(f"no peaks for device kind {device['kind']!r} in "
                       "peaks.json")
    # every metric that reads something is printed; the result holds
    # the end-to-end ones, or with tracing the per-layer ones
    read_all = {}
    for m in cell.end_to_end + cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        value = reader.read(run_rec)
        if value is not None:
            read_all[m["name"]] = {"value": value, "unit": m["unit"]}
    wanted = {m["name"] for m in (cell.per_layer if trace
                                  else cell.end_to_end)}
    metrics = {k: v for k, v in read_all.items() if k in wanted}
    dev = dict(device, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": len(queries),
              "failed": sum(not q.ok for q in queries),
              "metrics": metrics, "device": dev}
    if prof is not None and prof.reduction is not None:
        red = prof.reduction
        dev["busy_s"] = red["busy_ns"] / 1e9
        dev["window_s"] = red["window_ns"] / 1e9
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    for k, v in read_all.items():
        print(f"metric: {k} = {v['value']!r} {v['unit']}", file=log)
    result["check"] = {k: {"value": read[k], "limit": limits[k]}
                       for k in READINGS}
    result["check"]["answers"] = {"value": n_read, "limit": len(queries)}
    for k in READINGS:
        print(f"check: {k} = {read[k]!r} (limit {limits[k]!r})", file=log)
    print(f"check: {n_read} answers compared of {len(queries)} sent, "
          f"{len(errors)} failed; correct = {correct}", file=log)
    return result


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read about one run."""
    cell: Cell
    seconds: float
    setup_s: float
    queries: List[Query]
    window: tuple           # (start, end), host monotonic ns
    counters: Dict[str, float]
    device: Optional[dict]  # trace_reduce.reduce(...) of the window
    peak: Optional[dict]    # peaks.json entry of this device kind


class _Profiler:
    """A ``jax.profiler`` trace of the window, aligned to the host's
    monotonic clock by an annotation, and reduced to metrics."""

    def __init__(self, log_dir: Path):
        self.log_dir = log_dir
        self.reduction = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self.align = time.monotonic_ns()
        with jax.profiler.TraceAnnotation("bench:align"):
            pass

    def stop(self, window) -> None:
        import jax
        jax.profiler.stop_trace()
        self.window = window

    def reduce(self, spans) -> None:
        from trace_reduce import load_events, reduce
        events = load_events(self.log_dir)
        self.reduction = reduce(events, self.align, self.window, spans)
        shutil.rmtree(self.log_dir, ignore_errors=True)
