"""One run of one cell: set-up, the measured window, the check.

The cell (``BENCHMARK.json``'s ``workloads`` entry) names a configuration
(``configs/<config>.json``, with its rows and plain reference in
``configs/<config>.py``) and a traffic mix (``traffic/<traffic>.json``).
The harness drives only ``SketchFleetEngine``'s public calls:
``submit_many``, ``step``, ``query_user`` and ``query_cohort``.

Set-up: build the engine, make the row pool from the seed and fill the
configuration's ``fill_ticks`` ticks with uniform full slabs (this
compiles the update program, or loads it from the persistent cache).
Then the window: before each tick every stream is topped up to the
mix's ``pending_blocks`` blocks of pending rows, ``step()`` is called,
the tick is mirrored in the ``Ledger`` and its new state handed to a
waiter thread that records when it is ready on the device.  After the
window, rows still pending are drained (at most ``DRAIN_S`` seconds),
answers are drawn from the seed and compared with the configuration's
reference.

With ``trace`` the run goes on for ``TRACE_TICKS`` ticks of the same
traffic under the JAX profiler after the window closes; the per-layer
readers (``metrics/<name>.py``) read the window's host spans and that
trace.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import queue
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import workload
from ledger import Ledger

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".chipbench_cache" / "jax"
DRAIN_S = 60.0
TRACE_TICKS = 3


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, cell: str):
    """(end-to-end metrics, per-layer metrics) this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def merge(base: dict, extra: dict) -> dict:
    out = dict(base)
    for k, v in (extra or {}).items():
        out[k] = merge(base[k], v) if isinstance(v, dict) and isinstance(
            base.get(k), dict) else v
    return out


class Spans:
    """Host-clock spans ``(name, start, end, n)`` around each call into
    the engine; with ``annotate`` also written into the profiler trace."""

    def __init__(self):
        self.items = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str, n: int = 0):
        import jax

        ann = (jax.profiler.TraceAnnotation("chipbench." + name)
               if self.annotate else contextlib.nullcontext())
        with ann:
            t0 = time.perf_counter()
            yield t0
            self.items.append((name, t0, time.perf_counter(), n))


class Waiter(threading.Thread):
    """Blocks on each tick's fleet state; records when it is ready."""

    def __init__(self):
        super().__init__(daemon=True)
        self.inbox = queue.Queue()
        self.ready = {}

    def run(self):
        import jax

        while True:
            item = self.inbox.get()
            if item is None:
                return
            k, state = item
            jax.block_until_ready(state)
            self.ready[k] = time.perf_counter()

    def close(self):
        self.inbox.put(None)
        self.join()


def setup_jax(persistent_cache: bool):
    """JAX with its persistent compilation cache at a fixed path inside
    the checkout, so only a cell's first run there compiles (off for
    the CPU runs of the tests, which must not fill it)."""
    import jax

    if persistent_cache:
        CACHE_DIR.mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


class Compiles:
    """Backend compilations, counted from JAX's monitoring events."""

    def __init__(self, jax):
        self.n = 0

        def on(name, *_, **__):
            if name == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(on)


class Collections:
    """Pauses of Python's garbage collector, for the run's log line."""

    def __init__(self):
        self.pauses = []
        self._t0 = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def close(self):
        gc.callbacks.remove(self)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, allow_cpu: bool = False, overrides=None,
             fault=None, control: bool = False, log=print) -> dict:
    """One run; returns the result line's object (``checks`` last).

    The keyword arguments serve the control and fault tests, never the
    benchmark's own runs: ``allow_cpu`` skips the look for a chip,
    ``overrides`` (``{"config": {...}}``) changes the scale of a
    configuration, ``fault(engine)`` breaks the engine under the
    harness, and ``control`` adds the control's readings
    (``out["control"]``)."""
    bench = load_bench()
    cell = find_cell(bench, name)
    overrides = overrides or {}
    cfg = merge(workload.load_json(HERE / "configs" / f"{cell['config']}.json"),
                overrides.get("config"))
    ref = load_module(HERE / "configs" / f"{cell['config']}.py")
    mix = workload.load_mix(cell["traffic"])
    chips = int(cell["chips"])

    jax = setup_jax(not allow_cpu)
    compiles = Compiles(jax)
    devices = jax.devices()
    if not allow_cpu and (devices[0].platform != "tpu"
                          or len(devices) < chips):
        raise NoChip(f"cell {name} needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    devices = devices[:chips]
    kind = devices[0].device_kind
    peaks = json.loads((HERE / "peaks.json").read_text())["devices"]
    if not allow_cpu and kind not in peaks:
        raise NoChip(f"no peaks for device kind {kind!r} in peaks.json")

    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from jax.sharding import AxisType

    from repro.serve.engine import SketchFleetEngine

    sy = cfg["system"]
    S, block = int(sy["streams"]), int(sy["block"])
    mesh = jax.make_mesh((chips,), ("streams",), (AxisType.Auto,),
                         devices=devices)
    marks = {"devices": time.perf_counter()}
    eng = SketchFleetEngine(sy["variant"], d=int(sy["d"]), streams=S,
                            eps=float(sy["eps"]), window=int(sy["window"]),
                            block=block, mesh=mesh, ingest=sy["ingest"],
                            mode=sy["mode"])
    if fault is not None:
        fault(eng)
    marks["engine"] = time.perf_counter()
    rows = ref.Rows(cfg, seed)
    marks["rows"] = time.perf_counter()
    led = Ledger(S, block)
    spans = Spans()
    pending_rows = int(mix["arrivals"]["pending_blocks"]) * block

    def top_up():
        """Submit rows until every stream has ``pending_rows`` pending;
        returns (rows offered, rows refused)."""
        users = np.repeat(np.arange(S, dtype=np.int32),
                          np.maximum(pending_rows - led.pending, 0))
        if not users.size:
            return 0, 0
        batch = rows.make(users, led.ordinals(users))
        with spans("submit", int(users.size)):
            acc = eng.submit_many(users, batch)
        n = int(np.count_nonzero(acc))
        led.admit(users[:n])
        return int(users.size), int(users.size) - n

    # -- set-up: fill every window with uniform full slabs ------------------
    for _ in range(int(cfg["fill_ticks"])):
        users = workload.fill_users(S, block)
        assert eng.submit_many(users, rows.make(
            users, led.ordinals(users))).all()
        led.admit(users)
        t_before = eng.t
        led.tick(t_before, eng.step())
    jax.block_until_ready(eng.state)
    marks["fill"] = time.perf_counter()
    spans.items.clear()

    offered = refused = 0
    ticks = []            # dict(k, dispatch, ret, rows, phase)
    waiter = Waiter()
    waiter.start()
    compiles_before = compiles.n
    collections = Collections()

    t_start = time.perf_counter()
    setup_s = t_start - t_process
    marks["warm"] = t_start
    t_end = t_start + seconds

    def one_tick(phase: str):
        nonlocal offered, refused
        o, r = top_up()
        if phase == "window":
            offered, refused = offered + o, refused + r
        t_before = eng.t
        with spans("step") as t_dispatch:
            n = eng.step()
        led.tick(t_before, n)
        k = len(led.takes) - 1
        ticks.append({"k": k, "dispatch": t_dispatch,
                      "ret": time.perf_counter(), "rows": n,
                      "phase": phase})
        waiter.inbox.put((k, eng.state))

    while time.perf_counter() < t_end:
        one_tick("window")
    window_pauses = list(collections.pauses)
    window_spans = list(spans.items)
    compiles_in_window = compiles.n - compiles_before

    summary = None
    if trace:
        import jax.profiler

        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(tdir)
        spans.annotate = True
        with jax.profiler.TraceAnnotation("chipbench.window"):
            for _ in range(TRACE_TICKS):
                one_tick("trace")
            with spans("wait"):
                jax.block_until_ready(eng.state)
        spans.annotate = False
        jax.profiler.stop_trace()
        import devtrace

        try:
            summary = devtrace.reduce(devtrace.extract(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    # -- close: drain what is pending ----------------------------------------
    drain_stop = time.perf_counter() + DRAIN_S
    while led.pending.sum() and time.perf_counter() < drain_stop:
        t_before = eng.t
        n = eng.step()
        led.tick(t_before, n)
    jax.block_until_ready(eng.state)
    waiter.close()
    collections.close()
    for tk in ticks:
        tk["ready"] = waiter.ready.get(tk["k"], float("nan"))

    # -- end-to-end metrics ------------------------------------------------
    win_ticks = [tk for tk in ticks if tk["phase"] == "window"]
    # all the work dispatched in the window, over the time from its start
    # until the last of it was ready (a tick in flight at the close is
    # counted whole, with its time; whole ticks would quantize the rate)
    rows_in_window = sum(tk["rows"] for tk in win_ticks)
    t_done = max([tk["ready"] for tk in win_ticks], default=t_end)
    values = {"rows_per_s": rows_in_window / (t_done - t_start),
              "setup_s": setup_s}
    unabsorbed = int(led.pending.sum())

    # -- the check: answers drawn from the seed against the reference ------
    g = workload.rng(seed, 21)
    chk = cfg["check"]
    sample = []
    for u in g.choice(S, min(int(chk["users"]), S), replace=False):
        sample.append({"kind": "user", "users": (int(u),), "t": eng.t,
                       "B": eng.query_user(int(u))})
    for _ in range(int(chk["cohorts"])):
        lo = int(g.integers(0, S - int(chk["cohort_size"]) + 1))
        users = tuple(range(lo, lo + int(chk["cohort_size"])))
        sample.append({"kind": "cohort", "users": users, "t": eng.t,
                       "B": eng.query_cohort(list(users))})
    for a in sample:
        B = np.asarray(a.pop("B"), np.float64)
        a["gram"] = B.T @ B
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    engine_rows = int(eng.rows_ingested)
    del eng
    streams_cache = {}

    def history(u, t):
        if u not in streams_cache:
            streams_cache[u] = led.stream(u, 1 << 62)
        ords, ts = streams_cache[u]
        keep = ts <= t
        return (rows.make(np.full(int(keep.sum()), u), ords[keep]),
                ts[keep])

    numbers = ref.compare(sample, history, cfg)
    checks = {k: (v, float(cfg["limits"][k])) for k, v in numbers.items()}
    checks["tick_count_mismatches"] = (led.mismatches, 0)
    checks["rows_never_absorbed"] = (unabsorbed, 0)
    checks["engine_row_count_gap"] = (
        abs(engine_rows - int(np.stack(led.takes).astype(np.int64).sum())),
        0)
    correct = all(v <= lim for v, lim in checks.values()) and len(sample) > 0

    # -- the result line ---------------------------------------------------
    e2e, layer = cell_metrics(bench, name)
    out = {"correct": bool(correct), "attempted": offered,
           "failed": unabsorbed + refused}
    if not trace:
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]} for m in e2e}
    else:
        run = SimpleNamespace(
            cell=cell, cfg=cfg, system=sy, peaks=peaks.get(kind), S=S,
            block=block, seconds=seconds, t_start=t_start, t_end=t_end,
            spans=window_spans, ticks=ticks, trace=summary)
        metrics = {}
        if str(HERE / "metrics") not in sys.path:
            sys.path.insert(0, str(HERE / "metrics"))
        for m in layer:
            reader = load_module(HERE / "metrics" / f"{m['name']}.py")
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
    out["device"] = {"platform": devices[0].platform, "kind": kind,
                     "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        import devtrace

        out["device"]["busy_s"] = summary["busy_s"]
        out["device"]["window_s"] = summary["window_s"]
        out["breakdown"] = devtrace.breakdown(summary)
    if control:
        out["control"] = ref.compare(sample, history, cfg, control=True)
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    log(f"chipbench: {name} seed {seed}: {len(win_ticks)} ticks in the "
        f"window ({stall(win_ticks, t_start)}), "
        f"{compiles_in_window} compilations in it, "
        f"{len(window_pauses)} collector pauses in it (longest "
        f"{max([d for _, d in window_pauses], default=0.0):.3f} s), "
        f"{len(sample)} answers compared, setup {setup_s:.3f} s (" + ", ".join(
            f"{k} {v - t_process:.3f}" for k, v in marks.items()) + ")")
    return out


def stall(ticks, t_start) -> str:
    """The longest time from one tick's state being ready to the next's,
    split into the host's lateness in entering ``step()`` after the
    device went idle, ``step()``'s own time after that, and the rest
    until the state was ready."""
    prev, worst = t_start, None
    for tk in sorted(ticks, key=lambda tk: tk["ready"]):
        gap = tk["ready"] - prev
        if worst is None or gap > worst[0]:
            late = max(tk["dispatch"] - prev, 0.0)
            in_step = max(tk["ret"] - max(tk["dispatch"], prev), 0.0)
            worst = (gap, late, in_step, tk["ready"] - tk["ret"])
        prev = tk["ready"]
    if worst is None:
        return "no tick ready"
    return ("longest %.3f s from one ready to the next: host late %.3f, "
            "in step() %.3f, then %.3f until ready" % worst)
