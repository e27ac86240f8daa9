"""Self time per named scope from nested op sums and the program's HLO,
the readers of the engine's own record, and the recorded v5e trace read
by the readers that were there before them."""

import json
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import devtrace
import scopes

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"
P = "jit(update_block)/vmap(jit(update_block))"

# a program shaped like the fleet update: an unscoped row loop holding a
# dump fusion and an SVD loop, whose body holds a custom call and a copy
# the compiler added (no metadata: it takes its caller's scope)
HLO = f"""HloModule jit_update_block

%body.3 (p: f32[4]) -> f32[4] {{
  %custom-call.4 = f32[4]{{0}} custom-call(%p), custom_call_target="EighTpu", metadata={{op_name="{P}/while/body/dsfd.absorb/dsfd.rotate/svd/eigh"}}
  ROOT %copy.5 = f32[4]{{0}} copy(%custom-call.4)
}}

%cond.3 (p: f32[4]) -> pred[] {{
  ROOT %constant.9 = pred[] constant(false)
}}

%fused.2 (p: f32[4]) -> f32[4] {{
  ROOT %select.8 = f32[4]{{0}} select(%p, %p, %p), metadata={{op_name="{P}/while/body/dsfd.absorb/dsfd.dump/select_n"}}
}}

%body.1 (p: f32[4]) -> f32[4] {{
  %fusion.2 = f32[4]{{0}} fusion(%p), kind=kLoop, calls=%fused.2, metadata={{op_name="{P}/while/body/dsfd.absorb/dsfd.dump/select_n"}}
  ROOT %while.3 = f32[4]{{0}} while(%fusion.2), condition=%cond.3, body=%body.3, metadata={{op_name="{P}/while/body/dsfd.absorb/dsfd.rotate/while"}}
}}

%cond.1 (p: f32[4]) -> pred[] {{
  ROOT %constant.10 = pred[] constant(false)
}}

ENTRY %main.7 (a: f32[4]) -> f32[4] {{
  %fusion.6 = f32[4]{{0}} fusion(%a), kind=kLoop, calls=%fused.2, metadata={{op_name="{P}/broadcast_in_dim"}}
  %while.1 = f32[4]{{0}} while(%fusion.6), condition=%cond.1, body=%body.1, metadata={{op_name="{P}/while"}}
  ROOT %fusion.7 = f32[4]{{0}} fusion(%while.1), kind=kLoop, calls=%fused.2, metadata={{op_name="{P}/while/body/dsfd.expire/and"}}
}}
"""

# one execution, [0, 100) ns; the ops nest as the HLO says
OPS = [("%fusion.6", 0, 10), ("%while.1", 10, 80), ("%fusion.2", 15, 25),
       ("%while.3", 45, 40), ("%custom-call.4", 50, 25), ("%copy.5", 76, 8),
       ("%fusion.7", 90, 10)]
SELF_NS = {"unscoped": 10 + 15, "dsfd.dump": 25, "dsfd.rotate": 7 + 25 + 8,
           "dsfd.expire": 10}


def sums(ops, executions=1):
    out = {}
    for name, _, dur in ops:
        out[name] = out.get(name, 0.0) + float(dur) * executions
    return out


def test_parse_scopes_and_calls():
    h = scopes.parse(HLO)
    assert h["while.1"][0] is None
    assert sorted(h["while.1"][1]) == ["constant.10", "fusion.2", "while.3"]
    assert h["while.3"][0] == "dsfd.rotate"
    assert sorted(h["while.3"][1]) == ["constant.9", "copy.5",
                                       "custom-call.4"]
    # no metadata: the scope of the instruction that calls its body
    assert h["copy.5"] == ("dsfd.rotate", [])
    assert h["fusion.6"][0] is None and h["fusion.7"][0] == "dsfd.expire"


def test_self_time_per_scope_of_nested_ops():
    st = scopes.self_times(sums(OPS), scopes.parse(HLO))
    assert st == pytest.approx(SELF_NS)
    # the scopes' self times add up to the program's time
    assert sum(st.values()) == pytest.approx(100.0)


def test_scope_ms_per_tick_through_the_trace_reduction():
    """Three executions in a traced window, summed per op name by the
    reduction, give each scope's share of the program's device time."""
    events = [[HOST, "python", devtrace.WINDOW, 0.0, 400.0]]
    events += [[DEV, devtrace.MODULES, "jit_update_block(1)",
                100.0 * k + 50, 100.0] for k in range(3)]
    summary = devtrace.reduce({"events": events,
                               "ops_ns": {DEV: sums(OPS, executions=3)}})
    tick_s = float(np.mean(summary["programs_s"]["jit_update_block(1)"]))
    ms = scopes.per_tick_ms(summary, HLO, tick_s)
    assert ms == pytest.approx({k: v * 1e-6 for k, v in SELF_NS.items()})
    assert sum(ms.values()) == pytest.approx(tick_s * 1e3)


def test_an_op_begun_before_the_trace_leaves_its_children_theirs():
    """Two whole executions, and a third whose row loop, SVD loop and
    first fusion began before the profiler started: they have no events
    there, the ops inside them that began later do."""
    ops = sums(OPS, executions=2)
    for name in ("%fusion.2", "%custom-call.4", "%copy.5", "%fusion.7"):
        ops[name] += dict(sums(OPS))[name]
    st = scopes.self_times(ops, scopes.parse(HLO))
    # the loops lose self time to the orphans (the SVD loop down to 0,
    # not below); every op inside keeps its own
    assert st == pytest.approx({
        "unscoped": 2 * 10 + (2 * 80 - 3 * 25 - 2 * 40),
        "dsfd.dump": 3 * 25, "dsfd.rotate": 0 + 3 * 25 + 3 * 8,
        "dsfd.expire": 3 * 10})


def test_ops_of_other_programs_are_left_out():
    st = scopes.self_times(dict(sums(OPS), **{"%fusion.99": 50.0}),
                           scopes.parse(HLO))
    assert sum(st.values()) == pytest.approx(100.0)


def fixture_run(**extra):
    """The recorded trace as the harness hands it to a reader: the
    telemetry cell's shapes, three traced ticks of full slabs."""
    cfg = json.loads((DATA.parent.parent / "configs" / "telemetry-d64.json")
                     .read_text())
    peaks = json.loads((DATA.parent.parent / "peaks.json").read_text())
    summary = devtrace.reduce(json.loads(
        (DATA / "trace_uniform_sat_v5e.json").read_text()))
    ticks = [{"k": k, "phase": "trace", "rows": 256 * 8, "dispatch": 0.0,
              "ret": 0.0, "ready": 0.0} for k in range(3)]
    ticks += [{"k": 3 + k, "phase": "window", "rows": 256 * 8,
               "dispatch": 10.0 * k, "ret": 10.0 * k + 0.4,
               "ready": 10.0 * k + 0.9} for k in range(2)]
    run = SimpleNamespace(cfg=cfg, system=cfg["system"], S=256, block=8,
                          peaks=peaks["devices"]["TPU v5 lite"],
                          spans=[], ticks=ticks, trace=summary)
    for k, v in extra.items():
        setattr(run, k, v)
    return run


def read(metric, run):
    import harness

    return harness.load_module(harness.HERE / "metrics" /
                               f"{metric}.py").read(run)


def test_recorded_trace_gives_the_earlier_readers_their_values():
    run = fixture_run()
    assert read("update_device_ms.sat", run) == pytest.approx(
        (0.449013677 + 0.450453308 + 0.449074189) / 3 * 1e3, rel=1e-12)
    busy = run.trace["busy_s"]
    assert read("device_idle.sat", run) == pytest.approx(
        100.0 * (1.0 - busy / 1.744851854), rel=1e-12)
    assert read("tick_ms.sat", run) == pytest.approx(900.0, rel=1e-12)
    import cost

    least, _ = cost.least_seconds(run.system, 2048, 256, run.peaks)
    assert read("update_roofline.sat", run) == pytest.approx(
        100.0 * least / ((0.449013677 + 0.450453308 + 0.449074189) / 3),
        rel=1e-12)


def test_engine_readers_read_the_newest_tick_log():
    from repro.serve import trace

    log = trace.TickLog(slab_rows=16)
    for k, rows in enumerate((16, 8)):
        with log.step(clock=4 * k):
            for phase, busy in ((trace.NEXT_SLAB, 0.002),
                                (trace.PREFETCH, 0.001),
                                (trace.WAIT, 0.004)):
                with log.span(phase):
                    t = time.perf_counter()
                    while time.perf_counter() - t < busy:
                        pass
            log.taken(rows, rows // 4)
    rec = log.records()
    ticks = [{"phase": "window", "dispatch": r["start"] - 1e-6,
              "ret": r["end"] + 1e-6} for r in rec]
    run = SimpleNamespace(ticks=ticks + [{"phase": "trace",
                                          "dispatch": 0.0, "ret": 0.0}])
    assert read("slab_occupancy.sat", run) == pytest.approx(75.0)
    step = np.mean(rec["end"] - rec["start"]
                   - (rec["wait_end"] - rec["wait_start"])) * 1e3
    assert read("step_host_ms.sat", run) == pytest.approx(step)
    ingest = np.mean(rec["next_slab_end"] - rec["next_slab_start"]
                     + rec["prefetch_end"] - rec["prefetch_start"]) * 1e3
    assert read("ingest_host_ms.sat", run) == pytest.approx(ingest)
    assert 3.0 <= read("ingest_host_ms.sat", run) < read("step_host_ms.sat",
                                                          run)


def test_engine_readers_read_nothing_without_a_device_trace():
    run = fixture_run(trace=None)
    assert read("update_svd_ms.sat", run) is None
    assert read("update_dump_ms.sat", run) is None
