"""The traced run's burst schedule (`run.TraceSampler`), driven with a fake
profiler whose write takes longer than a sixteenth of a sweep, by a main
thread that sleeps through its population and sweeps as a run's would."""
import re
import time

import pytest

import run
import trace_reduce as tr

DEV = "/device:TPU:0"


class FakeProfiler:
    """Records each session as (path, start, end); stopping one takes
    `write_s`, as writing a trace does."""

    def __init__(self, write_s: float):
        self.write_s = write_s
        self.active = None
        self.sessions = []

    def start(self, path):
        assert self.active is None, "sessions overlap"
        self.active = (path, time.perf_counter())

    def stop(self):
        path, t0 = self.active
        time.sleep(self.write_s)
        self.active = None
        self.sessions.append((path, t0, time.perf_counter()))


def drive(sampler, sweep_s, population_s, first_s=None, max_sweeps=60):
    """Sweeps back to back, the first of `first_s` seconds (as long as the
    others unless given), until the sampler is done past sweep 2."""
    for k in range(1, max_sweeps + 1):
        sampler.population()
        time.sleep(population_s)
        sampler.sweep(k, first_s or sweep_s)
        time.sleep(first_s if k == 1 and first_s else sweep_s)
        if k >= 2 and not sampler.running():
            break
    sampler.close()
    return k


def sampler_of(tmp_path, write_s, bins=10, burst_s=0.01, max_s=30.0):
    s = run.TraceSampler(tmp_path / "trace", profiler=FakeProfiler(write_s))
    s.BINS, s.BURST_S, s.MAX_S = bins, burst_s, max_s
    return s


def test_slow_writes_take_every_bin(tmp_path):
    sweep_s = 0.6
    s = sampler_of(tmp_path, write_s=sweep_s / 16 * 1.5)
    drive(s, sweep_s, 0.02)
    assert not s.running()
    assert sorted(b.bin for b in s.bursts) == list(range(10))
    assert re.search(r"took 10 of 10 planned bursts .*\(0 not reached\)",
                     s.summary())
    # one session per burst, none closer to the last than its write
    sessions = s.profiler.sessions
    assert len(sessions) == 10
    for (_, _, end), (_, start, _) in zip(sessions, sessions[1:]):
        assert start >= end
    assert all(b.cost_s >= s.BURST_S + s.profiler.write_s for b in s.bursts)
    # together they cover the sweep
    fifths = {min(int(5 * b.phase), 4) for b in s.bursts}
    assert len(fifths) >= 4
    assert all(b.label.startswith("bench.sweep ") and
               int(b.label.split()[1]) >= 2 for b in s.bursts)


def test_a_capped_window_counts_what_it_missed(tmp_path):
    s = sampler_of(tmp_path, write_s=0.2, max_s=0.5)
    drive(s, 0.6, 0.02)
    taken = len(s.bursts)
    assert 1 <= taken < 10
    assert f"took {taken} of 10 planned bursts" in s.summary()
    assert f"({10 - taken} not reached)" in s.summary()


def burst_trace(label, busy):
    """A burst of 1000 ns on the device, busy for its first `busy` ns."""
    ops = [tr.Event(DEV, tr.OPS_LINE, "%f = f32[8] fusion(...)", 0.0,
                    float(busy))] if busy else []
    return tr.Trace(ops, span=(0.0, 1000.0), label=label)


def test_population_bursts_are_left_out_of_idle(tmp_path):
    """Sweeps that end before sweep 1's length put the last bins in the
    population between sweeps: those bursts keep the label, and the idle
    share counts only the bursts inside a sweep."""
    s = sampler_of(tmp_path, write_s=0.05)
    drive(s, sweep_s=0.3, population_s=0.3, first_s=0.6)
    labels = [b.label for b in s.bursts]
    assert "bench.population" in labels
    assert any(label.startswith("bench.sweep ") for label in labels)
    sample = tr.Sample([burst_trace(label, 0 if label == "bench.population"
                                    else 600) for label in labels])
    read = run.load_metric_reader("device_idle_share")
    assert read({"trace": sample}) == pytest.approx(0.4)
    assert sample.idle_share() > 0.4
    assert read({"trace": tr.Sample([burst_trace("bench.population", 0)])}) \
        is None
