"""One run of one benchmark cell: set-up, the measured window, the check.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic; ``configs/<config>.json``,
``mixes/<traffic>.json``, ``models/<family>.py`` and one
``metrics/<metric>.py`` per metric are read from the benchmark directory.

The window drives the program's served path: ``create_engine`` over the
configuration's backend, wrapped in a ``ServingFrontend``.  It is an open
loop over the run's precomputed arrivals:

1. every read that is due is submitted (``submit_read``, one in ten pinned
   a version back), then one ``serve_reads`` call serves them all: one
   gather of the union of their rows per pinned version;
2. every update event that is due, up to the mix's batch cap, goes into one
   ``UpdateBatch`` and through ``ServingFrontend.apply_batch``, and each of
   its events is stamped visible when the call returns;
3. where nothing is due, the loop sleeps until the next arrival.

Every request is timed from its due time.  When the window closes, what
was due in it and is still waiting is applied and served, up to a minute
past the close, and counts with its full wait.

With ``control`` the reference computed one precision step down (three
bfloat16 passes) stands in for what the window produced, the final
embeddings and every served read, and goes through the same comparison:
its ``correct`` has to come out false.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import itertools
import json
import resource
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import traffic as tr
from chipbench.graphs import load_dataset
from chipbench.reference import forward, pad_edges, relative_rms, scaled_gap

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIRNAME = "chipbench"
DRAIN_LIMIT_S = 60.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    model: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path

    def reader(self, metric: str) -> Callable:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py").read


def load_cell(workload: str, root: Path = ROOT,
              config_overrides: Optional[dict] = None,
              mix_overrides: Optional[dict] = None) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files.
    Overrides shrink a configuration or a mix for CPU rehearsals."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    bench = root / BENCH_DIRNAME
    config = json.loads((bench / "configs" / f"{w['config']}.json").read_text())
    for k, v in (config_overrides or {}).items():
        config[k] = {**config[k], **v} if isinstance(v, dict) else v
    mix = json.loads((bench / "mixes" / f"{w['traffic']}.json").read_text())
    mix.update(mix_overrides or {})
    model = load_module(bench / "models" / f"{config['family']}.py")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, int(w["chips"]), config, mix, model,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)], bench)


def load_peaks(bench_dir: Path, device_kind: str) -> dict:
    peaks = json.loads((bench_dir / "peaks.json").read_text())["devices"]
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in peaks.json; have {sorted(peaks)}")
    return peaks[device_kind]


def enable_compile_cache(root: Path) -> str:
    """Keep every compiled program in JAX's persistent cache, in a fixed
    directory of the checkout (the path is part of the cache key), so that
    only a checkout's first run compiles and no two checkouts share it.
    Entry points call this; ``run_cell`` leaves the process's JAX settings
    alone, except that no program compiled inside the window is written
    to the cache (see ``_no_cache_writes``)."""
    import jax

    path = str(root / BENCH_DIRNAME / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileLog:
    """XLA executables built (compiled, or read from the persistent cache),
    with the host time each was finished, through ``jax.monitoring``."""

    def __init__(self) -> None:
        import jax

        self.done: List[float] = []
        self.seconds: List[float] = []
        self.names: List[str] = []
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.done.append(time.perf_counter())
            self.seconds.append(duration)
            self.names.append(str(kw.get("fun_name", "?")))

    def between(self, t0: float, t1: float) -> List[int]:
        return [i for i, t in enumerate(self.done) if t0 <= t <= t1]

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on)


@dataclasses.dataclass
class Record:
    """What a run measured, in host seconds from the window's opening
    unless named otherwise; the metric readers take their numbers here."""

    setup_s: float
    seconds: float
    ev_due: np.ndarray
    ev_visible: np.ndarray  # NaN where never applied
    rd_due: np.ndarray
    rd_submitted: np.ndarray
    rd_served: np.ndarray  # NaN where never served
    batches: list  # [(BatchStats, lo, hi, start, end)] of the window's events
    serve_calls: list  # [(start, end, reads served)] of submit-and-serve rounds
    compiles: int
    device: dict
    batch_cpu: list = dataclasses.field(default_factory=list)  # rusage deltas
    trace: Optional[dict] = None
    flops: Optional[float] = None
    flops_window_s: Optional[float] = None
    peaks: Optional[dict] = None


@contextlib.contextmanager
def _no_cache_writes():
    """The program compiles gathers for each new write-set and read-union
    size inside the window.  Written to the persistent cache, they would be
    found by later runs of the checkout whose seeds hit the same sizes, so
    a window's cost would depend on which runs came before it, and those
    compiles would hide from ``compiles`` and the tails.  Reads from the
    cache are unchanged."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, key)
    jax.config.update(key, float("inf"))
    try:
        yield
    finally:
        jax.config.update(key, before)


def _make_inputs(cell: Cell, seed: int, n: int):
    """Features and weights on the device in one jitted call from the seed,
    in the configuration's ``dtype``."""
    import jax
    import jax.numpy as jnp

    dims = tuple(cell.config["dims"])
    dtype = jnp.dtype(cell.config["dtype"])
    key = int(np.random.default_rng(
        np.random.SeedSequence([seed, 1])).integers(0, 2**31 - 1))

    @jax.jit
    def make(k):
        kx, kp = jax.random.split(jax.random.PRNGKey(k))
        x = jax.random.normal(kx, (n, dims[0]), dtype)
        params = cell.model.init_params(kp, dims)
        return x, jax.tree.map(lambda a: a.astype(dtype), params)

    return make(key)


def _apply(fe, ev: tr.Events, lo: int, hi: int, pairs: np.ndarray):
    import jax

    from repro.graph.streaming import UpdateBatch

    b = tr.build_batch(ev, lo, hi, pairs)
    ub = UpdateBatch(b.ins_src, b.ins_dst, b.del_src, b.del_dst,
                     feat_vertices=b.feat_vertices, feat_values=b.feat_values)
    with jax.profiler.TraceAnnotation("chipbench/apply_batch"):
        return fe.apply_batch(ub)


@dataclasses.dataclass
class ServedRead:
    version: int
    rows: np.ndarray
    value: Optional[np.ndarray]


def _submit(fe, rows: np.ndarray, pinned: bool):
    pin = max(fe.version - 1, fe.min_version) if pinned else None
    return fe.submit_read(rows, version=pin)


class _GcLog:
    """Full (generation 2) collections of the garbage collector, timed."""

    def __init__(self) -> None:
        self.spans: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if info.get("generation") == 2:
            if phase == "start":
                self._t = time.perf_counter()
            else:
                self.spans.append((self._t, time.perf_counter()))

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def _cpu() -> tuple:
    r = resource.getrusage(resource.RUSAGE_SELF)
    return (r.ru_utime, r.ru_stime, r.ru_nivcsw, r.ru_majflt)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, t_process: Optional[float] = None,
             require_tpu: bool = True, config_overrides: Optional[dict] = None,
             mix_overrides: Optional[dict] = None, control: bool = False,
             log: Callable[[str], None] = lambda s: print(s, file=sys.stderr)
             ) -> dict:
    """One run of one cell; returns the result line as a dict (and, with
    ``control``, the control's readings under ``"control"``)."""
    t_process = time.perf_counter() if t_process is None else t_process
    import jax

    cell = load_cell(workload, root, config_overrides, mix_overrides)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {platform!r}")
    if len(devices) < cell.chips:
        raise NoAccelerator(f"cell needs {cell.chips} chip(s), JAX found "
                            f"{len(devices)}")
    kind = devices[0].device_kind
    peaks = load_peaks(cell.bench_dir, kind) if platform == "tpu" else None
    compiles = CompileLog()
    try:
        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            return _run(cell, seed, seconds, trace, root, t_process, control,
                        compiles, peaks, log)
    finally:
        compiles.close()


def _run(cell, seed, seconds, trace, root, t_process, control, compiles,
         peaks, log) -> dict:
    import jax

    from repro.core.models import make_model
    from repro.graph.csr import CSRGraph
    from repro.serve import EngineConfig, ServingFrontend, create_engine

    cfg, mix = cell.config, cell.mix
    ds = load_dataset(cfg["graph"], cell.bench_dir / ".graph_cache")
    n, d0 = ds.n, cfg["dims"][0]
    base = ds.pairs[:ds.num_base].astype(np.int64)
    graph = CSRGraph.from_edges(n, np.concatenate([base[:, 0], base[:, 1]]),
                                np.concatenate([base[:, 1], base[:, 0]]))
    x, params = _make_inputs(cell, seed, n)
    x_host = np.asarray(x)  # the reference's version-0 features
    eng = create_engine(cfg["backend"], EngineConfig(
        model=make_model(cell.model.ENGINE_MODEL), graph=graph, x=x,
        params=params))
    if eng.embeddings.dtype != np.dtype(cfg["dtype"]):
        raise ValueError(f"the engine keeps {eng.embeddings.dtype} embeddings; "
                         f"the configuration states {cfg['dtype']}")
    fe = ServingFrontend(eng, **cfg.get("serving", {}))
    del graph

    # warm-up: fixed batches from the configuration's graph seed, then one
    # fresh and one pinned read, so every shape the window drives compiles
    live = tr.LiveEdges(ds.num_base, ds.pairs.shape[0])
    wrng = np.random.default_rng(np.random.SeedSequence([cfg["graph"]["seed"], 7]))
    parts = tr.make_warmup(wrng, live, mix, n, d0)
    warm_rows = wrng.permutation(n)[:mix["read_rows"]]
    # the warm-up's events sit first in the run's event log; log_batches
    # holds the (lo, hi) events of each version, in order
    lo = 0
    log_batches = []
    for p in parts:
        _apply(fe, p, 0, len(p), ds.pairs)
        log_batches.append((lo, lo + len(p)))
        lo += len(p)
    for pinned in (False, True):
        _submit(fe, warm_rows, pinned)
        fe.serve_reads()
    jax.block_until_ready(eng.embeddings)

    # the run's own traffic: fixed amounts from the graph seed, put in
    # order and filled from the run's seed
    shape = np.random.default_rng(np.random.SeedSequence([cfg["graph"]["seed"], 11]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))
    wev = tr.make_window_events(shape, rng, live, mix, n, d0, seconds)
    reads = tr.make_reads(shape, rng, mix, n, seconds)
    events = tr.concat_events(parts + [wev])
    w0 = lo  # first window event
    n_ev, n_rd = len(wev), reads.due.shape[0]
    visible = np.full(n_ev, np.nan)
    submitted = np.full(n_rd, np.nan)
    served_t = np.full(n_rd, np.nan)
    tickets: List = [None] * n_rd
    waiting: List[int] = []  # reads submitted, not yet answered
    batches, serve_calls, batch_cpu = [], [], []
    cap = int(mix["batch_cap_events"])
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    gc.collect()

    def stamp(t_ret: float) -> None:
        """Reads answered by the frontend call that returned at ``t_ret``."""
        still = []
        for i in waiting:
            if tickets[i].result is not None:
                served_t[i] = t_ret
            elif tickets[i].error is None:
                still.append(i)
        waiting[:] = still

    def loop(t0: float, until: float, drain: bool) -> None:
        nonlocal ev_i, rd_i
        while True:
            now = time.perf_counter() - t0
            if not drain and now >= until:
                return
            if drain and (ev_i >= n_ev and rd_i >= n_rd):
                return
            if drain and now >= until:
                return
            if rd_i < n_rd and reads.due[rd_i] <= now:
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("chipbench/serve_reads"):
                    while rd_i < n_rd and reads.due[rd_i] <= now:
                        submitted[rd_i] = now
                        tickets[rd_i] = _submit(fe, reads.rows[rd_i],
                                                bool(reads.pinned[rd_i]))
                        waiting.append(rd_i)
                        rd_i += 1
                    k = fe.serve_reads()
                b = time.perf_counter()
                serve_calls.append((a - t0, b - t0, k))
                stamp(b - t0)
                now = b - t0
            hi = ev_i
            while hi < n_ev and hi - ev_i < cap and wev.due[hi] <= now:
                hi += 1
            if hi > ev_i:
                c = _cpu()
                a = time.perf_counter()
                bs = _apply(fe, events, w0 + ev_i, w0 + hi, ds.pairs)
                b = time.perf_counter()
                batch_cpu.append(tuple(y - x for x, y in zip(c, _cpu())))
                stamp(b - t0)
                visible[ev_i:hi] = b - t0
                batches.append((bs, ev_i, hi, a - t0, b - t0))
                log_batches.append((w0 + ev_i, w0 + hi))
                ev_i = hi
                continue
            nxt = min(reads.due[rd_i] if rd_i < n_rd else np.inf,
                      wev.due[ev_i] if ev_i < n_ev else np.inf, until)
            if drain and not np.isfinite(nxt):
                return
            wait = nxt - (time.perf_counter() - t0)
            if wait > 0:
                with jax.profiler.TraceAnnotation("chipbench/sleep"):
                    time.sleep(wait)

    ev_i = rd_i = 0
    gcs = _GcLog()
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    with _no_cache_writes():
        with jax.profiler.TraceAnnotation("chipbench/window"):
            loop(t0, seconds, drain=False)
        t_close = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        n_window_batches = len(batches)
        loop(t0, seconds + DRAIN_LIMIT_S, drain=True)
    gcs.close()
    full_gcs = [(a - t0, b - a) for a, b in gcs.spans if a <= t_close]
    log(f"full garbage collections in window: {len(full_gcs)}, "
        f"{sum(d for _, d in full_gcs)!r} s")
    built = compiles.between(t0, t_close)
    in_window = len(built)
    if built:
        names: Dict[str, int] = {}
        for i in built:
            names[compiles.names[i]] = names.get(compiles.names[i], 0) + 1
        log(f"compiled in window: {in_window} executables, "
            f"{sum(compiles.seconds[i] for i in built)!r} s: {names}")

    stats = devices_memory()
    final_version = fe.version
    final = np.asarray(eng.embeddings)
    checked = [ServedRead(t.version, reads.rows[i], t.result)
               for i, t in enumerate(tickets) if t is not None]
    unserved = int(np.isnan(served_t).sum())
    unapplied = int(np.isnan(visible).sum())
    del fe, eng, x
    gc.collect()

    rec = Record(setup_s=setup_s, seconds=seconds, ev_due=wev.due,
                 ev_visible=visible, rd_due=reads.due, rd_submitted=submitted,
                 rd_served=served_t, batches=batches, serve_calls=serve_calls,
                 compiles=in_window, device=stats, peaks=peaks,
                 batch_cpu=batch_cpu)
    log(f"window: {n_window_batches} batches in {seconds} s, "
        f"{len(batches) - n_window_batches} after the close; events "
        f"{n_ev} due, reads {n_rd} due; compiles in window {in_window}")

    # the check: the reference replays the event log, version by version
    params_h = jax.tree.map(np.asarray, params)
    program, ctl = check(cell, ds, params_h, x_host, events, log_batches,
                         final_version, final, checked, control)
    readings = ctl if control else program
    if control:
        log("control: the reference at three bfloat16 passes stands in for "
            f"the window's output; the program read final_rms "
            f"{program['final_rms']!r}, read_err {program['read_err']!r}")
    limits = cell.config["limits"]
    log(f"final embeddings: largest scaled gap {readings['final_max']!r} "
        "(not compared: it swings from seed to seed as much as the control's)")
    checks = {
        "final_rms": {"value": readings["final_rms"], "limit": limits["final_rms"]},
        "read_err": {"value": readings["read_err"], "limit": limits["read_err"]},
        "unserved_reads": {"value": unserved, "limit": 0},
        "unapplied_events": {"value": unapplied, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    if trace:
        splits = [(b[0].graph_time_s, b[0].plan_time_s, b[0].exec_time_s)
                  for b in batches[:n_window_batches]]
        rec.trace = _reduce(trace_dir, splits)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if peaks is not None:
            rec.flops = window_flops(cell, ds, events, log_batches, w0,
                                     batches[:n_window_batches])
            rec.flops_window_s = t_close - t0

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    _log_summary(rec, log)
    if rec.trace is not None:
        log(f"device idle by host span, s: {rec.trace['idle_by_span']}")
    device = {"platform": stats["platform"], "kind": stats["kind"],
              "count": stats["count"],
              "memory_peak_bytes": stats["memory_peak_bytes"]}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
    out = {"correct": bool(correct), "attempted": int(n_ev + n_rd),
           "failed": unserved + unapplied, "metrics": metrics,
           "device": device}
    if trace and rec.trace is not None:
        out["breakdown"] = {"device_ops": rec.trace["device_ops"],
                            "idle_gaps": rec.trace["idle_gaps"]}
    if control:
        out["program"] = program
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    return out


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _reduce(trace_dir: str, splits) -> Optional[dict]:
    from chipbench import trace_reduce

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    return trace_reduce.reduce_trace(trace_reduce.load(files[-1]), splits)


def devices_memory() -> dict:
    import jax

    devs = jax.devices()
    peak = None
    stats = [d.memory_stats() for d in devs]
    if all(s is not None for s in stats):
        peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _base_alive(ds) -> np.ndarray:
    return np.r_[np.ones(ds.num_base, bool), np.zeros(ds.num_pool, bool)]


def _replay(ds, events: tr.Events, log_batches, x0: Optional[np.ndarray]):
    """Yield (version, alive per undirected edge, features) after each
    batch of the log, from the base graph and the seed's features (none
    where ``x0`` is None)."""
    alive = _base_alive(ds)
    x = None if x0 is None else x0.copy()
    for v, (lo, hi) in enumerate(log_batches, start=1):
        for i in range(lo, hi):
            k = events.kind[i]
            if k == tr.INSERT:
                alive[events.edge[i]] = True
            elif k == tr.DELETE:
                alive[events.edge[i]] = False
            elif x is not None:
                x[events.vertex[i]] = events.values[events.feat[i]]
        yield v, alive, x


def check(cell, ds, params, x0: np.ndarray, events, log_batches,
          final_version, final: np.ndarray, reads: List[ServedRead],
          control: bool):
    """Compare the final embeddings and every served read with the
    reference at their versions.  With ``control``, also compare the
    reference computed one precision step down (three bfloat16 passes)."""
    import jax
    import jax.numpy as jnp

    src, dst = ds.directed()
    s_pad, d_pad, block = pad_edges(src, dst)
    e = src.shape[0]
    src_d, dst_d = jnp.asarray(s_pad), jnp.asarray(d_pad)
    want: Dict[int, List[ServedRead]] = {}
    for r in reads:
        if r.value is not None:
            want.setdefault(r.version, []).append(r)
    need = set(want) | {final_version}
    dots = ["highest"] + (["three_pass"] if control else [])
    worst = {d: {"final_rms": 0.0, "final_max": 0.0, "read_err": 0.0}
             for d in dots}
    start = [(0, _base_alive(ds), x0)]
    for v, alive_u, x in itertools.chain(
            start, _replay(ds, events, log_batches[:final_version], x0)):
        if v not in need:
            continue
        alive = np.zeros(s_pad.shape[0], np.float32)
        alive[:e] = np.concatenate([alive_u, alive_u])
        args = (params, jnp.asarray(x), src_d, dst_d, jnp.asarray(alive))
        ref = np.asarray(forward(cell.model.layer, "highest", block, *args))
        outs = {"highest": None}
        if control:
            outs["three_pass"] = np.asarray(
                forward(cell.model.layer, "three_pass", block, *args))
        for d in dots:
            for r in want.get(v, []):
                got = r.value if d == "highest" else outs[d][r.rows]
                worst[d]["read_err"] = max(worst[d]["read_err"],
                                           scaled_gap(got, ref[r.rows]))
            if v == final_version:
                got = final if d == "highest" else outs[d]
                worst[d]["final_rms"] = relative_rms(got, ref)
                worst[d]["final_max"] = scaled_gap(got, ref)
        del ref, outs
    return worst["highest"], worst.get("three_pass")


def window_flops(cell, ds, events, log_batches, w0, window_batches) -> float:
    """Model FLOPs the window's batches require: for each layer, the rows
    whose output the batch changes under the model's semantics (the
    model file's ``cone``), 2·d_in·d_out per weight matrix each, plus d_in
    per live in-edge of each.  Counted from the benchmark's own replay,
    never from the program's plan."""
    dims = cell.config["dims"]
    n = ds.n
    src, dst = ds.directed()
    first = {lo for (_, lo, _, _, _) in window_batches}
    total = 0.0
    prev = _base_alive(ds)
    for v, alive_u, _ in _replay(ds, events, log_batches, None):
        lo, hi = log_batches[v - 1]
        cur = alive_u.copy()
        if lo - w0 in first:
            alive_new = np.concatenate([cur, cur])
            changed_u = cur != prev
            touched = np.zeros(n, bool)
            touched[ds.pairs[changed_u].ravel()] = True
            deg_new = np.bincount(dst[alive_new], minlength=n)
            alive_old = np.concatenate([prev, prev])
            deg_old = np.bincount(dst[alive_old], minlength=n)
            fi = np.arange(lo, hi)[events.kind[lo:hi] == tr.FEATURE]
            masks = cell.model.cone(src, dst, alive_new, touched,
                                    deg_new != deg_old, events.vertex[fi], n,
                                    len(dims) - 1)
            for l, m in enumerate(masks):
                d_in, d_out = dims[l], dims[l + 1]
                rows = int(m.sum())
                total += (rows * 2.0 * d_in * d_out * cell.model.MATRICES_PER_LAYER
                          + d_in * float(deg_new[m].sum()))
        prev = cur
    return total


def _log_summary(rec: Record, log) -> None:
    vis = rec.ev_visible - rec.ev_due
    rd = rec.rd_served - rec.rd_due
    late = rec.rd_submitted - rec.rd_due
    taken = np.full(rec.ev_due.shape, np.nan)
    for _, lo, hi, start, _ in rec.batches:
        taken[lo:hi] = start
    # how late the loop ran: due → the moment it took the request
    for name, arr in (("update_visible_s", vis), ("read_s", rd),
                      ("update_take_late_s", taken - rec.ev_due),
                      ("read_submit_late_s", late)):
        ok = arr[np.isfinite(arr)]
        if ok.size:
            log(f"{name}: n={ok.size} median={float(np.median(ok))!r} "
                f"p95={float(np.percentile(ok, 95))!r} max={float(ok.max())!r}")
        else:
            log(f"{name}: n=0")
    # user and system CPU seconds of the whole process, involuntary context
    # switches and major faults over each batch: a batch whose wall time
    # far exceeds its CPU time waited for the host, not for its own work
    for k, ((bs, lo, hi, a, b), cpu) in enumerate(zip(rec.batches, rec.batch_cpu)):
        log(f"batch {k}: events={hi - lo} start={a:.3f} end={b:.3f} "
            f"graph_s={bs.graph_time_s:.3f} plan_s={bs.plan_time_s:.3f} "
            f"exec_s={bs.exec_time_s:.3f} records={bs.inc_edges + bs.full_edges} "
            f"out_rows={bs.out_vertices} user_s={cpu[0]:.3f} sys_s={cpu[1]:.3f} "
            f"nivcsw={cpu[2]} majflt={cpu[3]}")
    served = [k for _, _, k in rec.serve_calls if k > 0]
    if served:
        log(f"reads: {sum(served)} served in {len(served)} serve_reads calls, "
            f"largest {max(served)}")
    sizes = [hi - lo for (_, lo, hi, _, _) in rec.batches]
    if sizes:
        log(f"batches: n={len(sizes)} events median={float(np.median(sizes))} "
            f"max={max(sizes)}")
    log(f"setup_s={rec.setup_s!r} memory_peak_bytes="
        f"{rec.device['memory_peak_bytes']}")
