"""One run of one cell: set-up, the measured window, the check against
the plain reference, and the metrics.

The program is reached only through this interface (nothing else of it
is imported here):

    repro.core.params.make_params            the HE parameter set
    repro.engine.backend.BFVBackend          keys, encryption, HE ops
    repro.engine.schema.ColumnSpec, TableSchema
    repro.engine.storage.Database.load_table encrypt a table
    repro.engine.planner.Planner             optimized planner + mask cache
    repro.engine.plan (QueryPlan, Pred, And, Agg, Factor)
    repro.engine.executor.run_via_plan       compile -> verify -> execute
                                             -> decrypt
    repro.engine.executor.Executor.compile,
    repro.engine.verify.verify_compiled      admission, timed apart

plus its counters (`BFVBackend.stats`, an OpStats) and the ciphertext
arrays a loaded table holds.

A configuration whose `planner` states `shards` runs every planner with
that many data shards, one per chip of the cell.  On such a mesh
`stored_bytes_per_row` is still the table's ciphertext bytes, summed
over every chip that holds them, over its rows, and `memory_peak_bytes`
is the peak of the fullest chip.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import os
import shutil
import statistics
import sys
import tempfile
import time
import traceback

import numpy as np

from . import lineitem, querygen, reference, xplane
from .peaks import peaks

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
MAXIMA = ("max_depth",)    # OpStats fields that are maxima, not counters
ADMIT_MIN_S = 0.25        # host clock: repeat admission for at least this long


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Programs compiled or loaded from the compile cache while active,
    and the host pauses that can hold a dispatch back with the device
    idle: a function traced or lowered again (a tracing-cache miss)
    and Python garbage collections, each as (name, start offset from
    `t0` in s, seconds)."""

    def __init__(self):
        self.active = False
        self.t0 = time.perf_counter()
        self.names: list[str] = []
        self.traces: list = []
        self.gcs: list = []
        self._gc_start = None
        gc.callbacks.append(self._gc)

    def __call__(self, event: str, duration: float, **kw) -> None:
        if not self.active:
            return
        if event == BACKEND_COMPILE:
            self.names.append(str(kw.get("fun_name")))
        elif event in (TRACE, LOWER):
            now = time.perf_counter()
            self.traces.append((f"{event.rsplit('/', 1)[1]} "
                                f"{kw.get('fun_name')}",
                                now - duration - self.t0, duration))

    def _gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_start = now
        elif self.active and self._gc_start is not None:
            self.gcs.append((f"gen{info['generation']}",
                             self._gc_start - self.t0, now - self._gc_start))

    def start(self) -> None:
        for events in (self.names, self.traces, self.gcs):
            events.clear()
        self.t0 = time.perf_counter()
        self.active = True

    def close(self) -> None:
        self.active = False
        gc.callbacks.remove(self._gc)

    def pauses(self) -> str:
        """One line: the window's traces and collections, longest first."""
        top = lambda xs: [(n, round(at, 3), round(d, 4)) for n, at, d in
                          sorted(xs, key=lambda x: -x[2])[:5]]
        return (f"traced or lowered {len(self.traces)} "
                f"({sum(d for *_, d in self.traces):.4f} s) "
                f"{top(self.traces)}; collections {len(self.gcs)} "
                f"({sum(d for *_, d in self.gcs):.4f} s) {top(self.gcs)}")


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader (`bench/metrics/<name>.py`) sees."""
    cell: str
    queries: int
    window_s: float
    ops: collections.Counter      # every OpStats counter's delta over
                                  # the window (a field the program lacks
                                  # reads 0); maxima as after the window
    window_compiles: list         # programs compiled or loaded in it
    admit_s: list | None          # host seconds per admission (traced run)
    trace: "xplane.Summary | None"
    memory_peak_bytes: int
    peaks: dict | None


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_backend(cfg: dict, seed: int, phases: dict):
    from repro.core.params import make_params
    from repro.engine.backend import BFVBackend
    he = cfg["he"]
    t0 = time.perf_counter()
    params = make_params(n=he["n"], t=he["t"], k=he["k"], qbits=he["qbits"])
    phases["params"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    bk = BFVBackend(params, seed=int(seed) % (1 << 63))
    import jax
    jax.block_until_ready(bk.keys.rlk.b)
    phases["keygen"] = time.perf_counter() - t0
    return bk


def load_table(bk, cfg: dict, cols: dict):
    from repro.engine.schema import ColumnSpec, TableSchema
    from repro.engine.storage import Database
    schema = TableSchema(cfg["table"], [
        ColumnSpec(c["name"], c["kind"], scale=c.get("scale", 1))
        for c in cfg["columns"]])
    db = Database(bk)
    db.load_table(schema, lineitem.program_columns(cols, cfg["columns"]),
                  int(cfg["rows"]))
    return db


def ct_nbytes(ct) -> int:
    return int(ct.data.nbytes)


def stored_bytes(db, table: str) -> int:
    """Bytes of the table's ciphertext arrays as held on the device."""
    import jax
    tbl = db.tables[table]
    blocks = [b for c in tbl.columns.values() for b in c.blocks]
    jax.block_until_ready([b.data for b in blocks])
    return sum(ct_nbytes(b) for b in blocks)


def memory_peak() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


class Client:
    """The one closed-loop client: issues the mix's queries through the
    engine's normal path and keeps what each returned."""

    def __init__(self, db, cfg: dict, mix: dict, seed: int):
        self.db, self.cfg, self.mix = db, cfg, mix
        self.traffic = querygen.Traffic(mix, seed)
        self.shared = self._planner() if mix["planner"] == "shared" else None

    def _planner(self):
        """A planner as the configuration states it: `planner.shards`,
        where given, partitions every stacked column over a data mesh of
        that many chips; without it the planner runs on one chip."""
        from repro.engine.planner import Planner
        conf = self.cfg["planner"]
        kw = {"shards": int(conf["shards"])} if "shards" in conf else {}
        return Planner(self.db, optimized=bool(conf["optimized"]), **kw)

    def planner(self):
        return self.shared if self.shared is not None else self._planner()

    def query(self, warmup: bool = False) -> dict:
        """One query: {params, args, plan, result | None, error | None}.
        A warm-up query runs the mix's warm-up plan."""
        from repro.engine.executor import run_via_plan
        params, args = self.traffic.next()
        plan = querygen.plan(self.mix["query"], args)
        if warmup:
            plan = querygen.warmup_plan(plan, self.mix["warmup_plan"])
        q = {"params": params, "args": args, "plan": plan, "result": None,
             "error": None}
        with annotate("query"):
            try:
                with annotate("planner"):
                    pl = self.planner()
                with annotate("run_via_plan"):
                    q["result"] = run_via_plan(pl, plan, verify=True)
            except Exception:      # a failed query is counted, not fatal
                q["error"] = traceback.format_exc()
                print(q["error"], file=sys.stderr, flush=True)
        return q


def admission_seconds(client: Client, plan) -> list:
    """Host seconds of Executor.compile + verify_compiled on the window's
    plan, with the planner in the window's cache state."""
    from repro.engine.executor import Executor
    from repro.engine.verify import verify_compiled
    pl = client.planner()
    out, total = [], 0.0
    while total < ADMIT_MIN_S or len(out) < 3:
        t0 = time.perf_counter()
        cq = Executor(pl).compile(plan)
        verify_compiled(pl, cq, mirror_begin_run=True)
        out.append(time.perf_counter() - t0)
        total += out[-1]
    return out


def _ops(bk) -> dict:
    """Every field of the backend's OpStats, by name."""
    return {f.name: getattr(bk.stats, f.name)
            for f in dataclasses.fields(bk.stats)}


def window_ops(before: dict, after: dict) -> collections.Counter:
    """Each counter's delta from `before` to `after`; a maximum keeps
    its value at `after`.  A name neither holds reads 0."""
    return collections.Counter({
        f: v if f in MAXIMA else v - before[f] for f, v in after.items()})


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_start: float, device_prefix: str = "/device:TPU:"):
    """Run one cell once; returns (record, result fields, checks)."""
    import jax
    from jax import monitoring

    counter = CompileCounter()
    monitoring.register_event_duration_secs_listener(counter)
    phases: dict = {"init": time.perf_counter() - t_start}
    with annotate("setup"):
        bk = make_backend(cfg, seed, phases)
        t0 = time.perf_counter()
        cols = lineitem.generate(cfg, seed)
        db = load_table(bk, cfg, cols)
        nbytes = stored_bytes(db, cfg["table"])
        phases["load"] = time.perf_counter() - t0
        client = Client(db, cfg, mix, seed)
        ops0 = _ops(bk)
        t0 = time.perf_counter()
        counter.active = True
        for _ in range(int(mix["warmup_queries"])):
            w = client.query(warmup=True)
            if w["error"] is not None:
                raise RuntimeError("warm-up query failed")
        phases["warmup"] = time.perf_counter() - t0
        phases["warmup_compiles"] = len(counter.names)
        counter.active = False
    setup_s = time.perf_counter() - t_start

    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir, profiler_options=_profile_options())
    queries, durations = [], []
    ops_w0 = _ops(bk)
    counter.start()
    t_w0 = time.perf_counter()
    with annotate("window"):
        while (not durations or statistics.fmean(durations)
               <= seconds - (time.perf_counter() - t_w0)):
            t0 = time.perf_counter()
            queries.append(client.query())
            durations.append(time.perf_counter() - t0)
    window_s = time.perf_counter() - t_w0
    counter.close()
    ops_w1 = _ops(bk)
    summary = admit = None
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak()
    if trace:
        admit = admission_seconds(client, queries[-1]["plan"])
        t0 = time.perf_counter()
        try:
            summary = xplane.reduce(xplane.find(tdir), device_prefix=device_prefix)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.3f} s")
        log(f"trace by name: scope_s {summary.scope_s}; span_s "
            f"{summary.span_s}; collective_s {summary.collective_s} "
            f"collective_bytes {summary.collective_bytes}")

    # --- the check, after the window and the memory reading ----------
    t = int(cfg["he"]["t"])
    wrong, expected, failed = 0, 0, 0
    for q in queries:
        want = reference.answer(cols, t, mix["query"], q["args"])
        if q["error"] is not None:
            bad, n = reference.wrong_values({}, want)
        else:
            bad, n = reference.wrong_values(q["result"], want)
        wrong, expected = wrong + bad, expected + n
        failed += int(bad > 0 or q["error"] is not None)
    refreshes = ops_w1["refresh"] - ops0["refresh"]
    checks = {"wrong_values": {"value": wrong, "limit": 0},
              "refreshes": {"value": refreshes, "limit": 0}}

    ops = window_ops(ops_w0, ops_w1)
    log(f"setup phases (s): {phases}")
    log(f"window: {len(queries)} queries in {window_s:.6f} s, durations "
        f"{[round(d, 6) for d in durations]}, programs compiled or loaded "
        f"in the window: {len(counter.names)} {sorted(set(counter.names))}")
    log(f"window op counts: {dict(ops)}; values compared: {expected}")
    log(f"window host pauses (offset from window start): {counter.pauses()}")
    log("params: " + "; ".join(str(q["params"]) for q in queries[:8])
        + (" ..." if len(queries) > 8 else ""))
    device = jax.devices()[0]
    rec = RunRecord(
        cell=cell["name"], queries=len(queries), window_s=window_s, ops=ops,
        window_compiles=list(counter.names),
        admit_s=admit, trace=summary, memory_peak_bytes=peak,
        peaks=peaks(device.device_kind) if device.platform == "tpu" else None)
    e2e = {
        "query_s": window_s / len(queries),
        "query_p95_s": float(np.percentile(durations, 95)),
        "stored_bytes_per_row": nbytes / int(cfg["rows"]),
        "setup_s": setup_s,
    }
    return rec, e2e, checks, failed


def read_metric(metrics_dir: str, name: str, rec: RunRecord):
    """Load `bench/metrics/<name>.py` and call its `read(record)`."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)
