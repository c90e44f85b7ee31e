"""The per-stage reading of the trace (`stage_trace`) and its readers, on
hand-built traces whose numbers are worked out below by hand, on a trace
recorded here on the CPU, and in a traced rehearsal of a whole run."""
import sys
import types

import pytest

import cells
import run
import stage_trace as stt
import trace_reduce as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
STK = "jit(_sim_batch_stacked)/vmap(jit(_one_sim_stacked))"
READERS = ("stacked_us_per_cycle", "solo_us_per_cycle",
           "stacked_admit_us_per_cycle", "stacked_select_us_per_cycle",
           "stacked_engine_us_per_cycle")


def ev(line, name, s, e, plane=DEV):
    return tr.Event(plane, line, name, float(s), float(e))


def stacked_run():
    """The stacked program over [0, 1000]: a loop of 3 iterations, each
    running its condition `%lt` (10 ns), `%e` under step.engine (50 ns),
    `%a` under step.admit (40 ns) and `%s` under step.select (100 ns), then
    an epilogue `%c` (20 ns) under no scope (`NAMES`)."""
    out = [ev(tr.MODULES_LINE, "jit__sim_batch_stacked(1)", 0, 1000),
           ev(tr.OPS_LINE, "%while.1 = (s32[]) while(...)", 10, 910),
           ev(tr.OPS_LINE, "%c = f32[9] fusion(...)", 920, 940)]
    for i in range(3):
        b = 10 + 300 * i
        out += [ev(tr.OPS_LINE, "%lt = pred[] compare(...)", b, b + 10),
                ev(tr.OPS_LINE, "%e = s32[9] fusion(...)", b + 20, b + 70),
                ev(tr.OPS_LINE, "%a = s32[9] fusion(...)", b + 80, b + 120),
                ev(tr.OPS_LINE, "%s = s32[9] fusion(...)", b + 130,
                   b + 230)]
    return out


# the stacked program's instructions and their op_names, as `hlo_op_names`
# reads them from its optimized HLO
HLO = f"""
  %lt = pred[] compare(s32[] %x, s32[] %n), metadata={{op_name="{STK}/while/cond/lt"}}
  %e = s32[9] fusion(...), kind=kLoop, metadata={{op_name="{STK}/while/body/closed_call/step.engine/add" source_file="engine.py"}}
  %a = s32[9] fusion(...), kind=kLoop, calls=%f.1, backend_config={{"k":{{"v":1}}}}, metadata={{op_name="{STK}/while/body/closed_call/step.admit/select_n"}}
  ROOT %s = s32[9] fusion(...), metadata={{op_name="{STK}/while/body/closed_call/step.select/select.score/reduce_max"}}
  %c = f32[9] fusion(...), metadata={{op_name="{STK}/div"}}
  %w = (s32[]) while(...), condition=%cond, body=%body
"""
NAMES = {stt.STACKED: stt.hlo_op_names(HLO)}


def solo_run():
    """The per-policy program over [1200, 1500], with no op_names: a loop
    of 2 iterations of `%d` (100 ns)."""
    return [ev(tr.MODULES_LINE, "jit__sim_batch(2)", 1200, 1500),
            ev(tr.OPS_LINE, "%while.2 = (s32[]) while(...)", 1200, 1500),
            ev(tr.OPS_LINE, "%d = s32[9] fusion(...)", 1200, 1300),
            ev(tr.OPS_LINE, "%d = s32[9] fusion(...)", 1400, 1500)]


def host_spans():
    """`bench.sweep 2` over the whole [0, 1600]; the program's `sweep`
    span over [950, 1590], with `sweep.fetch` over [1000, 1200]."""
    return [ev(HOST, "bench.sweep 2", 0, 1600, plane=HOST),
            ev(HOST, "sweep", 950, 1590, plane=HOST),
            ev(HOST, "sweep.fetch", 1000, 1200, plane=HOST)]


def hand_trace():
    return tr.Trace(stacked_run() + solo_run() + host_spans())


def test_hlo_op_names():
    assert NAMES[stt.STACKED] == {
        "%lt": f"{STK}/while/cond/lt",
        "%e": f"{STK}/while/body/closed_call/step.engine/add",
        "%a": f"{STK}/while/body/closed_call/step.admit/select_n",
        "%s": f"{STK}/while/body/closed_call/step.select/select.score/"
              "reduce_max",
        "%c": f"{STK}/div"}


def test_scope_time_and_per_cycle():
    t = hand_trace()
    # engine 3*50, admit 3*40, select 3*100; the 3 conditions and the
    # epilogue under no scope: 3*10 + 20
    assert stt.scope_time(t, NAMES) == {
        "_sim_batch_stacked": {"step.engine": 150.0, "step.admit": 120.0,
                               "step.select": 300.0, stt.UNSCOPED: 50.0},
        "_sim_batch": {stt.UNSCOPED: 200.0}}
    # the stacked loop's iterations are its condition's 3 runs; the solo
    # program shows no condition, so `loop_iterations` counts d d: 2
    assert stt.iterations(t, NAMES) == {"_sim_batch_stacked": 3.0,
                                 "_sim_batch": 2.0}
    s = stt.StageSample([t, t], NAMES)
    assert s.us_per_cycle(stt.STACKED) == pytest.approx(620 / 3 / 1e3)
    assert s.us_per_cycle(stt.SOLO) == pytest.approx(0.1)
    assert s.scope_us_per_cycle(stt.STACKED, "step.admit") == \
        pytest.approx(0.04)
    assert s.scope_us_per_cycle(stt.STACKED, "step.select") == \
        pytest.approx(0.1)
    assert s.scope_us_per_cycle(stt.STACKED, "step.engine") == \
        pytest.approx(0.05)
    assert s.scope_us_per_cycle(stt.STACKED, "step.skip") == 0.0
    assert s.unscoped_share(stt.STACKED) == pytest.approx(50 / 620)
    # the solo program has no op_names
    assert s.scope_us_per_cycle(stt.SOLO, "step.admit") is None
    assert s.unscoped_share(stt.SOLO) == 1.0
    # as trace_reduce reads it
    assert t.family_stats()["_sim_batch_stacked"] == \
        {"op_ns": 620.0, "iterations": 3.0}


def test_step_scope_is_innermost():
    assert stt.step_scope("a/while/body/step.skip/step.telemetry/add") == \
        "step.telemetry"
    assert stt.step_scope("a/step.select/pol.select/sms.stage3/x") == \
        "step.select"
    assert stt.step_scope("a/while/cond/lt") == stt.UNSCOPED
    assert stt.step_scope("") == stt.UNSCOPED


def test_gap_labels():
    gaps = stt.gaps(hand_trace())
    by = {}
    for label, ns in gaps:
        by[label] = by.get(label, 0.0) + ns
    # inside the stacked program: [0, 10] before the loop; in each of the
    # first two iterations 10 + 10 + 10 between its ops and 70 to the next;
    # in the third 30, and 80 from its `%s` (840) to `%c` (920)
    assert by["_sim_batch_stacked:in_program"] == pytest.approx(
        10 + 100 + 100 + (10 + 10 + 10 + 80))
    # [940, 1200], from the epilogue past the program's end: its midpoint
    # 1070 lies under sweep.fetch, inside sweep and bench.sweep 2
    assert by["sweep.fetch"] == pytest.approx(260)
    # [1300, 1400] inside the solo program
    assert by["_sim_batch:in_program"] == pytest.approx(100)
    # [1500, 1600]: its midpoint 1550 lies under the sweep span
    assert by["sweep"] == pytest.approx(100)
    assert sum(by.values()) == pytest.approx(1600 - 820)
    s = stt.StageSample([hand_trace()], NAMES)
    assert s.idle_gaps(2) == [["sweep.fetch", pytest.approx(260e-9)],
                              ["_sim_batch:in_program",
                               pytest.approx(100e-9)]]
    assert s.idle_by_label() == pytest.approx(
        {k: v / 1e9 for k, v in by.items()})


def test_gaps_without_device_ops():
    t = tr.Trace(host_spans(), span=(0.0, 1600.0), label="bench.population")
    assert stt.gaps(t) == [("bench.population", 1600.0)]


@pytest.fixture
def readers(monkeypatch):
    """The readers of `BENCHMARK.json`, with `from_ctx` handing them the
    sample the test sets in `box`."""
    box = {}
    monkeypatch.setattr(stt, "from_ctx", lambda ctx: box.get("sample"))
    return box, {name: run.load_metric_reader(name) for name in READERS}


def test_readers_on_hand_trace(readers):
    box, rd = readers
    box["sample"] = stt.StageSample([hand_trace()], NAMES)
    got = {name: f({}) for name, f in rd.items()}
    assert got == pytest.approx({
        "stacked_us_per_cycle": 620 / 3 / 1e3, "solo_us_per_cycle": 0.1,
        "stacked_admit_us_per_cycle": 0.04,
        "stacked_select_us_per_cycle": 0.1,
        "stacked_engine_us_per_cycle": 0.05})


def test_readers_without_their_scope(readers):
    box, rd = readers
    # no bursts at all
    assert all(f({}) is None for f in rd.values())
    # the stacked program without op_names (as from a program built without
    # scopes), and no per-policy program
    box["sample"] = stt.StageSample([tr.Trace(stacked_run())])
    got = {name: f({}) for name, f in rd.items()}
    assert got["stacked_us_per_cycle"] == pytest.approx(620 / 3 / 1e3)
    assert {k for k, v in got.items() if v is None} == {
        "solo_us_per_cycle", "stacked_admit_us_per_cycle",
        "stacked_select_us_per_cycle", "stacked_engine_us_per_cycle"}
    # only the per-policy program
    box["sample"] = stt.StageSample([tr.Trace(solo_run())])
    got = {name: f({}) for name, f in rd.items()}
    assert got["solo_us_per_cycle"] == pytest.approx(0.1)
    assert sum(v is None for v in got.values()) == 4


def _rec(i, event, sweep_id, dur_s, **kw):
    return {"ts": 0.0, "event": event, "id": i, "sweep_id": sweep_id,
            "parent": None if event == "sweep" else sweep_id,
            "dur_s": dur_s, **kw}


def test_sweep_host_s(monkeypatch):
    read = run.load_metric_reader("sweep_host_s")
    ctx = {"cell": types.SimpleNamespace(name="c")}
    records = [
        _rec(0, "sweep", 0, 9.0, tag="c"),          # set-up's warm-up
        _rec(1, "sweep.pools", 0, 1.0), _rec(2, "sweep.rows", 0, 2.0),
        _rec(3, "sweep", 3, 9.0, tag="c"),
        _rec(4, "sweep.pools", 3, 0.5), _rec(5, "sweep.fetch", 3, 7.0),
        _rec(6, "sweep.rows", 3, 0.25), _rec(7, "sweep.rows", 3, 0.25),
        _rec(8, "sweep", 8, 9.0, tag="c"),
        _rec(9, "sweep.pools", 8, 0.25), _rec(10, "sweep.rows", 8, 0.75),
        _rec(11, "sweep", 11, 9.0, tag="other"),
        _rec(12, "sweep.rows", 11, 5.0)]
    fake = types.SimpleNamespace(SPANS=types.SimpleNamespace(
        records=records))
    monkeypatch.setitem(sys.modules, "benchmarks.common", fake)
    # window sweeps 3 and 8: (0.5 + 0.25 + 0.25 + 0.25 + 0.75) / 2
    assert read(ctx) == pytest.approx(1.0)
    fake.SPANS.records = records[:3]                 # set-up's sweep only
    assert read(ctx) is None
    monkeypatch.setitem(sys.modules, "benchmarks.common",
                        types.SimpleNamespace())     # a program without
    assert read(ctx) is None


def test_stacked_op_names_of_the_program():
    """The map `from_ctx` joins to the trace, at a tiny size on the CPU:
    the stacked family program's instructions carry the step scopes."""
    import dataclasses

    cell = cells.load_cell("paper16.fig4")
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, population=dict(cell.traffic["population"],
                                      n_per_cat=1), n_cycles=8, warmup=2))
    names = stt.stacked_op_names(cell)
    assert all(k.startswith("%") for k in names)
    scopes = {stt.step_scope(v) for v in names.values()}
    assert {"step.engine", "step.admit", "step.select", stt.UNSCOPED} <= \
        scopes
    assert any(stt.is_loop_cond(v) for v in names.values())


def test_burst_dir_is_the_samplers():
    cell = cells.load_cell("paper16.fig4")
    assert stt.burst_dir(cell) == run.CACHE / "trace" / "paper16.fig4"


def test_load_xplane_keeps_program_spans(tmp_path):
    """A trace recorded here on the CPU: the `bench.` and `sweep` host
    spans are kept, nested as they ran, and no device plane is read."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.sweep 2"), \
            jax.profiler.TraceAnnotation("sweep"), \
            jax.profiler.TraceAnnotation("sweep.fetch"):
        f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tr.xplane_paths(str(tmp_path))
    t = stt.load_xplane(path, label="bench.sweep 2")
    spans = {e.name: e for e in t.events if stt.is_host_span(e.name)}
    assert set(spans) == {"bench.sweep 2", "sweep", "sweep.fetch"}
    assert spans["bench.sweep 2"].start_ns <= spans["sweep"].start_ns <= \
        spans["sweep.fetch"].start_ns <= spans["sweep.fetch"].end_ns <= \
        spans["sweep"].end_ns <= spans["bench.sweep 2"].end_ns
    assert t.planes == []
    assert stt.StageSample([t]).us_per_cycle(stt.STACKED) is None


def test_traced_rehearsal_reads_program_spans():
    """A traced run on the CPU at a tiny size: the bursts hold no TPU
    plane, so the device readers read nothing and are left out of the line,
    and `sweep_host_s` reads the window sweeps' spans."""
    import dataclasses
    import io
    import json
    from contextlib import redirect_stdout

    cell = cells.load_cell("paper16.fig4")
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, population=dict(cell.traffic["population"],
                                      n_per_cat=1), n_cycles=40, warmup=10))
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.run(cell, 2**31 + 11, 0.01, trace=True, platform="cpu")
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] is True
    assert set(res["metrics"]) == {"trace_lower_s", "compile_load_s",
                                   "sweep_host_s"}
    assert res["metrics"]["sweep_host_s"]["unit"] == "s"
    assert res["metrics"]["sweep_host_s"]["value"] > 0
