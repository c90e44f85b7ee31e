"""`run_sweep`'s host spans (`benchmarks.common.trace_span`): one tiny
sweep on the CPU lands its spans, nested, on the profiler trace's host plane
and, with their sweep id and parent, in the JSONL trace; `REPRO_TRACE=0`
writes no file and the sweep still runs."""
import json

import jax
import pytest

from benchmarks import common
from repro.core import workloads as wl

POLICIES = ["frfcfs", "atlas", "sms"]      # one stacked and one solo program
PHASES = {"sweep.pools", "sweep.dispatch", "sweep.fetch", "sweep.rows"}


def _sweep():
    cfg = common.parity_config()
    mixes = wl.make_workloads(cfg.n_cpu, n_per_cat=1)[:3]
    with common.throwaway_cache():
        return common.run_sweep(cfg, POLICIES, mixes, n_cycles=40, warmup=10,
                                tag="spans", force=True)


@pytest.fixture
def span_log(tmp_path, monkeypatch):
    """A fresh span log writing under the test's own directory."""
    monkeypatch.setattr(common, "SPANS", common.SpanLog())
    monkeypatch.setattr(common, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(common, "_TRACE_FILE", None)
    return common.SPANS


def test_spans_reach_profiler_and_jsonl(span_log, tmp_path, monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setenv("REPRO_TRACE", "1")
    prof = tmp_path / "prof"
    jax.profiler.start_trace(str(prof))
    try:
        res = _sweep()
    finally:
        jax.profiler.stop_trace()
    assert sorted(res) == sorted(POLICIES)

    (path,) = prof.rglob("*.xplane.pb")
    host = [e for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name == "sweep" or e.name in PHASES]
    names = [e.name for e in host]
    assert names.count("sweep") == 1
    assert set(names) == PHASES | {"sweep"}
    assert names.count("sweep.fetch") == names.count("sweep.rows") == 3
    assert names.count("sweep.dispatch") == 2
    (root,) = [e for e in host if e.name == "sweep"]
    for e in host:
        assert root.start_ns <= e.start_ns <= e.end_ns <= root.end_ns

    (jsonl,) = (tmp_path / "trace").glob("*.jsonl")
    recs = [json.loads(line) for line in jsonl.read_text().splitlines()]
    spans = [r for r in recs if r["event"] == "sweep" or r["event"] in PHASES]
    assert sorted(r["event"] for r in spans) == sorted(names)
    (top,) = [r for r in spans if r["event"] == "sweep"]
    assert top["parent"] is None and top["sweep_id"] == top["id"]
    assert top["tag"] == "spans" and top["errors"] == []
    for r in recs:
        assert r["sweep_id"] == top["id"]
        if r is not top:
            assert r["parent"] is not None
    assert all(r["parent"] == top["id"] for r in spans if r is not top)
    assert all(r["dur_s"] >= 0 for r in spans)
    # the same records stay in memory for readers in the process
    assert [r["id"] for r in span_log.records] == [r["id"] for r in recs]


def test_repro_trace_off_writes_no_file(span_log, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert sorted(_sweep()) == sorted(POLICIES)
    assert not (tmp_path / "trace").exists()
    assert {r["event"] for r in span_log.records} >= PHASES | {"sweep"}
