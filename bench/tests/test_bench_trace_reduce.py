"""The trace reduction, on hand-built traces whose numbers are worked out
below by hand."""
import pytest

import trace_reduce as tr

DEV = "/device:TPU:0"


def ev(line, name, s, e, plane=DEV):
    return tr.Event(plane, line, name, float(s), float(e))


def hand_trace():
    """Two programs. The stacked one runs a loop of 3 iterations, each
    running `%a` (100 ns) and `%b` (100 ns), then `%c` (20 ns) once after
    the loop, as an epilogue would. The per-policy one runs a loop of 2
    iterations of `%d` (100 ns). Host spans cover the whole of [0, 1600]."""
    out = [ev(tr.MODULES_LINE, "jit__sim_batch_stacked(1)", 0, 1000),
           ev(tr.OPS_LINE, "%while.1 = (s32[], ...) while(...)", 10, 910),
           ev(tr.MODULES_LINE, "jit__sim_batch(2)", 1200, 1500),
           ev(tr.OPS_LINE, "%while.2 = (s32[]) while(...)", 1200, 1500),
           ev(tr.OPS_LINE, "%c = s32[4] fusion(...)", 920, 940),
           ev("/host:CPU", "bench.sweep 2", 0, 1100, plane="/host:CPU"),
           ev("/host:CPU", "bench.population", 1100, 1150,
              plane="/host:CPU"),
           ev("/host:CPU", "bench.sweep 3", 1150, 1600, plane="/host:CPU")]
    for i in range(3):
        out += [ev(tr.OPS_LINE, "%a = f32[9] fusion(...)",
                   10 + 300 * i, 110 + 300 * i),
                ev(tr.OPS_LINE, "%b = s32[9] fusion(...)",
                   150 + 300 * i, 250 + 300 * i)]
    out += [ev(tr.OPS_LINE, "%d = s32[9] fusion(...)", 1200, 1300),
            ev(tr.OPS_LINE, "%d = s32[9] fusion(...)", 1400, 1500)]
    return out


def test_hand_trace_arithmetic():
    t = tr.Trace(hand_trace())
    assert t.window() == (0.0, 1600.0)
    # the loops enclose their bodies, so they are not leaf ops
    assert sorted(tr.op_label(o.name) for o in t.ops(DEV)) == \
        ["%a"] * 3 + ["%b"] * 3 + ["%c"] + ["%d"] * 2
    # busy: 3*100 + 3*100 + 20 + 2*100 = 820 ns of 1600
    assert t.busy_s() == pytest.approx(820e-9)
    s = tr.Sample([t])
    assert s.idle_share() == pytest.approx(1 - 820 / 1600)
    # stacked: ops a b a b a b c; a and b each ran 3 times, 2 ops apart,
    # so 6 / 2 = 3 iterations; c ran once and does not count.
    # per-policy: d d, 1 op apart: 2 / 1 = 2 iterations
    fs = t.family_stats()
    assert fs["_sim_batch_stacked"] == {"op_ns": 620.0, "iterations": 3}
    assert fs["_sim_batch"] == {"op_ns": 200.0, "iterations": 2}
    assert s.family_stats() == fs
    top = s.top_ops(3)
    assert [name for name, _ in top] == ["_sim_batch_stacked:%a",
                                         "_sim_batch_stacked:%b",
                                         "_sim_batch:%d"]
    assert [x for _, x in top] == pytest.approx([300e-9, 300e-9, 200e-9])
    # longest idle stretches: [940, 1200] in sweep 2's span, then
    # [1300, 1400] and [1500, 1600] in sweep 3's
    assert s.idle_gaps(3) == [["bench.sweep 2", pytest.approx(260e-9)],
                              ["bench.sweep 3", pytest.approx(100e-9)],
                              ["bench.sweep 3", pytest.approx(100e-9)]]


def test_loop_iterations():
    # a burst across the end of the warm-up loop (ops w, x) into the
    # measured loop (ops m, n): 3 + 4 iterations, each op 2 apart
    names = ["w", "x"] * 3 + ["m", "n"] * 4
    assert tr.loop_iterations(names) == 7
    # cut mid-iteration: the 6 ops it holds make 3 iterations
    assert tr.loop_iterations(["b", "a"] * 3) == 3
    assert tr.loop_iterations(["p", "q", "r"]) == 0


def test_bursts_add_up():
    """A second burst in which the device ran nothing is idle throughout,
    and is labelled by what the host was doing when it began."""
    hand = tr.Trace(hand_trace())
    quiet = tr.Trace([], span=(0.0, 400.0), label="bench.population")
    s = tr.Sample([hand, quiet, hand])
    assert s.busy_s() == pytest.approx(2 * 820e-9)
    assert s.window_s() == pytest.approx((1600 + 400 + 1600) * 1e-9)
    assert s.idle_share() == pytest.approx(1 - 1640 / 3600)
    assert s.family_stats()["_sim_batch_stacked"] == \
        {"op_ns": 1240.0, "iterations": 6}
    assert s.idle_gaps(2) == [["bench.population", pytest.approx(400e-9)],
                              ["bench.sweep 2", pytest.approx(260e-9)]]


def test_no_device_ops_reads_nothing():
    host = tr.Sample([tr.Trace([e for e in hand_trace()
                                if e.plane == "/host:CPU"])])
    assert host.idle_share() is None
    assert host.busy_s() == 0.0
    assert host.family_stats() == {}
    assert host.top_ops() == []
    assert host.idle_gaps() == [["host", pytest.approx(1600e-9)]]



def test_bursts_holding_each_family():
    hand = tr.Trace(hand_trace())
    stacked_only = tr.Trace([e for e in hand_trace()
                             if e.start_ns < 1000 or e.plane != DEV])
    quiet = tr.Trace([], span=(0.0, 400.0), label="bench.population")
    s = tr.Sample([hand, stacked_only, quiet])
    assert s.bursts_holding() == {"_sim_batch_stacked": 2, "_sim_batch": 1}
    assert tr.Sample([quiet]).bursts_holding() == {}
