"""Observability subsystem: tracer ring, meters registry, HTTP endpoints,
structured logger, trace analyzer, and the ledger's overhead surfacing."""
import importlib.util
import json
import logging
import os
import urllib.request

import pytest

from repro.comm.channel import InProcessChannel
from repro.obs import (Tracer, get_logger, merge_traces, read_trace_jsonl,
                       write_chrome_trace)
from repro.obs.meters import MetricsRegistry
from repro.obs.trace import _NOOP_SPAN

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "scripts", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


def test_span_records_tags_and_monotonic_interval():
    t = Tracer(enabled=True, proc="p1")
    with t.span("phase", round=3) as sp:
        sp.end(bytes=17)          # idempotent: __exit__ after end() is a no-op
    recs = t.drain()
    assert len(recs) == 1
    r = recs[0]
    assert r["kind"] == "span" and r["name"] == "phase" and r["proc"] == "p1"
    assert r["round"] == 3 and r["bytes"] == 17
    assert isinstance(r["t0"], int) and r["t1"] >= r["t0"]
    assert t.drain() == []        # drain cleared the ring


def test_event_records_instant():
    t = Tracer(enabled=True, proc="w")
    t.event("rx_frame", round=1, client=2, bytes=99, outcome="ok")
    (r,) = t.drain()
    assert r["kind"] == "event" and r["outcome"] == "ok" and "t" in r


def test_disabled_tracer_is_noop_and_allocation_free():
    t = Tracer(enabled=False)
    sp = t.span("x", round=0)
    assert sp is _NOOP_SPAN       # shared object: no per-call allocation
    with sp:
        sp.end(bytes=1)
    t.event("y")
    assert t.to_dicts() == []


def _profiled_host_events(tmp_path, body):
    """Run ``body()`` under a ``jax.profiler`` session; returns the host
    events of the trace as (name, {stat: value})."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return [(e.name, dict(e.stats))
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


@pytest.mark.parametrize("ring", [False, True])
def test_span_lands_in_a_profiler_trace(tmp_path, ring):
    """Under a profiler session a span is also a TraceAnnotation, with the
    ring on or off; outside one a disabled tracer's span is the no-op."""
    t = Tracer(enabled=ring)

    def body():
        with t.span("engine.dispatch", block=10, rounds_done=30) as sp:
            sp.end(extra=1)

    events = _profiled_host_events(tmp_path, body)
    got = [stats for name, stats in events if name == "engine.dispatch"]
    assert got == [{"block": 10, "rounds_done": 30}]
    assert [r["name"] for r in t.drain()] == (["engine.dispatch"] if ring
                                              else [])
    if not ring:
        assert t.span("engine.dispatch", rounds_done=0) is _NOOP_SPAN


def test_watch_compiles_counts_a_fresh_jit():
    import jax
    import numpy as np

    from repro.obs import get_registry, get_tracer, set_registry, set_tracer
    from repro.obs.meters import watch_compiles

    old_registry, old_tracer = get_registry(), get_tracer()
    registry, tracer = MetricsRegistry(), Tracer(enabled=True)
    set_registry(registry)
    set_tracer(tracer)
    try:
        watch_compiles()
        watch_compiles()                    # one listener per process
        jax.jit(lambda x: x * 3.0 + 1.0)(np.ones((7, 3), np.float32))
        snap = registry.snapshot()
        assert snap["counters"]["jax.compiles"] == 1
        assert snap["histograms"]["jax.compile_s"]["count"] == 1
        assert snap["histograms"]["jax.compile_s"]["sum"] > 0
        (ev,) = [r for r in tracer.drain() if r["name"] == "jax.compile"]
        assert ev["kind"] == "event" and ev["seconds"] > 0
    finally:
        set_registry(old_registry)
        set_tracer(old_tracer)


def test_ring_bounds_memory_and_counts_drops():
    t = Tracer(enabled=True, capacity=4)
    for i in range(10):
        t.event("e", i=i)
    recs = t.drain()
    assert len(recs) == 4
    assert [r["i"] for r in recs] == [6, 7, 8, 9]     # oldest evicted
    assert t.dropped == 6                              # eviction is visible


def test_jsonl_roundtrip(tmp_path):
    t = Tracer(enabled=True)
    with t.span("a", k="v"):
        pass
    t.event("b")
    path = str(tmp_path / "trace.jsonl")
    assert t.write_jsonl(path) == 2
    back = read_trace_jsonl(path)
    assert [r["name"] for r in back] == ["a", "b"]


def test_merge_traces_shifts_worker_clocks():
    server = [{"kind": "span", "name": "round", "proc": "server",
               "t0": 1000, "t1": 2000, "round": 0}]
    worker = {"client-1": [
        {"kind": "span", "name": "worker.compute", "proc": "client-1",
         "t0": 100, "t1": 200, "round": 0},
        {"kind": "event", "name": "ef_push", "proc": "client-1", "t": 300}]}
    merged = merge_traces(server, worker, {"client-1": 1_000_000})
    by_name = {r["name"]: r for r in merged}
    assert by_name["worker.compute"]["t0"] == 1_000_100
    assert by_name["worker.compute"]["t1"] == 1_000_200
    assert by_name["ef_push"]["t"] == 1_000_300
    assert by_name["round"]["t0"] == 1000                 # server untouched
    # sorted by start time
    starts = [r.get("t0", r.get("t")) for r in merged]
    assert starts == sorted(starts)


def test_chrome_trace_export(tmp_path):
    recs = [
        {"kind": "span", "name": "round", "proc": "server",
         "t0": 5_000_000, "t1": 9_000_000, "round": 0},
        {"kind": "event", "name": "rx_frame", "proc": "client-0",
         "t": 6_000_000, "bytes": 4},
    ]
    path = str(tmp_path / "t.json")
    n = write_chrome_trace(recs, path)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert n == len(evs)
    metas = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in metas} == {"server", "client-0"}
    x = next(e for e in evs if e["ph"] == "X")
    assert x["ts"] == 0.0 and x["dur"] == 4000.0          # rebased, us units
    i = next(e for e in evs if e["ph"] == "i")
    assert i["ts"] == 1000.0 and i["args"]["bytes"] == 4


# ---------------------------------------------------------------------------
# meters
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)                   # get-or-create: same instance
    reg.gauge("g").set(2.5)
    h = reg.histogram("h")
    for v in range(100):
        h.observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 100 and hs["min"] == 0.0 and hs["max"] == 99.0
    assert 45 <= hs["p50"] <= 55 and 90 <= hs["p95"] <= 99
    assert hs["p99"] >= hs["p95"] >= hs["p50"]


def test_histogram_ring_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("h", capacity=8)
    for v in range(100):
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100                  # count/sum track everything
    assert s["p50"] >= 92.0                   # quantiles from the recent ring


def test_sources_polled_and_exception_captured():
    reg = MetricsRegistry()
    reg.register_source("ok", lambda: {"x": 1})

    def boom():
        raise RuntimeError("dead source")

    reg.register_source("bad", boom)
    snap = reg.snapshot()
    assert snap["sources"]["ok"] == {"x": 1}
    assert "RuntimeError" in snap["sources"]["bad"]["error"]
    reg.unregister_source("bad")
    assert "bad" not in reg.snapshot()["sources"]


def test_http_endpoints():
    pytest.importorskip("http.server")
    from repro.obs.http import ObsHTTPServer

    reg = MetricsRegistry()
    reg.counter("hits").inc(3)
    srv = ObsHTTPServer(port=0, registry=reg)
    try:
        with urllib.request.urlopen(f"{srv.url}/healthz", timeout=5) as r:
            health = json.loads(r.read())
        assert health["status"] == "ok" and health["uptime_s"] >= 0
        with urllib.request.urlopen(f"{srv.url}/metrics", timeout=5) as r:
            snap = json.loads(r.read())
        assert snap["counters"]["hits"] == 3
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)
        assert ei.value.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# structured logger
# ---------------------------------------------------------------------------


def test_logger_prefixes_context():
    # the "repro" root logger is propagate=False (it owns its stderr
    # handler), so capture on the named logger itself
    records = []

    class Collect(logging.Handler):
        def emit(self, rec):
            records.append(rec.getMessage())

    log = get_logger("worker", client=7)
    h = Collect()
    log.logger.addHandler(h)
    try:
        log.info("hello %d", 42)
        log.bind(round=3).info("served")
    finally:
        log.logger.removeHandler(h)
    assert records[0] == "[client=7] hello 42"
    assert records[1] == "[client=7 round=3] served"


# ---------------------------------------------------------------------------
# trace analyzer (scripts/trace_report.py)
# ---------------------------------------------------------------------------


def _synthetic_trace():
    """Two rounds, three clients: round 0 all delivered; round 1 has a
    straggler (cid 1, worker busy > deadline), a dead worker (cid 2), and a
    filtered frame is recorded against round 0 for byte totals."""
    S = 1_000_000_000                                  # 1s in ns
    recs = []

    def span(name, t0, t1, **tags):
        recs.append({"kind": "span", "name": name, "proc": "server",
                     "t0": t0, "t1": t1, **tags})

    def ev(name, t, **tags):
        recs.append({"kind": "event", "name": name, "proc": "server",
                     "t": t, **tags})

    for rnd, base in ((0, 0), (1, 2 * S)):
        span("round", base, base + S, round=rnd, deadline_s=0.5)
        for i, ph in enumerate(("encode", "broadcast", "collect", "ack",
                                "aggregate")):
            span(f"round.{ph}", base + i * 1000, base + i * 1000 + 500,
                 round=rnd, phase=ph)
        for cid in range(3):
            ev("tx_frame", base + 100, round=rnd, client=cid, bytes=200)
    # round 0: all frames arrive ok, plus one filtered duplicate
    for cid in range(3):
        ev("rx_frame", 500_000, round=0, client=cid, bytes=100, outcome="ok")
        ev("round.outcome", S, round=0, client=cid, outcome="delivered")
    ev("rx_frame", 600_000, round=0, client=0, bytes=100, outcome="filtered")
    # round 1: cid 0 ok, cid 1 straggles, cid 2 dead
    ev("rx_frame", 2 * S + 500_000, round=1, client=0, bytes=100,
       outcome="ok")
    ev("round.outcome", 3 * S, round=1, client=0, outcome="delivered")
    ev("round.outcome", 3 * S, round=1, client=1, outcome="undelivered")
    ev("round.outcome", 3 * S, round=1, client=2, outcome="dead")
    # the straggler's own (merged) spans overrun the 0.5s deadline
    recs.append({"kind": "span", "name": "worker.compute", "proc": "client-1",
                 "t0": 2 * S, "t1": 2 * S + 300_000_000, "round": 1})
    recs.append({"kind": "span", "name": "worker.straggle", "proc": "client-1",
                 "t0": 2 * S + 300_000_000, "t1": 4 * S, "round": 1,
                 "sleep_s": 1.7})
    return recs


def test_trace_report_phases_and_attribution():
    tr = _load_trace_report()
    recs = _synthetic_trace()
    rep = tr.report(recs)
    assert rep["rounds"] == [0, 1]
    assert rep["phase_complete"] and rep["missing_phases"] == {}
    assert rep["phases"]["round"]["count"] == 2
    assert abs(rep["phases"]["round"]["p50"] - 1.0) < 1e-6    # 1s spans
    att = rep["attribution"]
    assert att["stragglers"] == {1: [1]}
    assert att["dead_workers"] == {2: [1]}
    assert att["frame_lost"] == {}            # the filtered frame was a dup
    causes = {(c["round"], c["client"]): c["cause"]
              for c in att["undelivered"]}
    assert causes == {(1, 1): "straggler", (1, 2): "dead"}


def test_trace_report_detects_missing_phase():
    tr = _load_trace_report()
    recs = [r for r in _synthetic_trace()
            if not (r.get("name") == "round.ack" and r.get("round") == 1)]
    rep = tr.report(recs)
    assert not rep["phase_complete"]
    assert rep["missing_phases"] == {"1": ["round.ack"]}


def test_trace_report_reconciliation_exact_and_mismatch():
    tr = _load_trace_report()
    recs = _synthetic_trace()
    # trace saw 5 rx frames x 100B (incl. the filtered one: it was billed)
    # and 6 tx frames x 200B
    good = {"uplink": {"total_bytes": 500}, "downlink": {"total_bytes": 1200},
            "overhead_up": 77, "overhead_down": 88}
    rec = tr.reconcile(recs, good)
    assert rec["uplink_exact"] and rec["downlink_exact"]
    assert rec["overhead_up"] == 77 and rec["overhead_down"] == 88
    bad = {"uplink": {"total_bytes": 501}, "downlink": {"total_bytes": 1200}}
    rec = tr.reconcile(recs, bad)
    assert not rec["uplink_exact"] and rec["downlink_exact"]


def test_trace_report_replay_summary():
    tr = _load_trace_report()
    rep = tr.replay_summary(_synthetic_trace())
    assert rep["schema"] == "repro.trace-replay/v1"
    assert [r["round"] for r in rep["rounds"]] == [0, 1]
    r0 = rep["rounds"][0]
    assert r0["wall_s"] == 1.0 and r0["deadline_s"] == 0.5
    assert r0["bytes_up"] == 400 and r0["bytes_down"] == 600
    assert r0["clients"]["0"]["outcome"] == "delivered"
    assert abs(r0["clients"]["0"]["arrival_s"] - 0.0005) < 1e-9
    r1 = rep["rounds"][1]
    assert r1["clients"]["1"]["outcome"] == "undelivered"
    assert r1["clients"]["1"]["arrival_s"] is None


def test_trace_report_cli(tmp_path):
    tr = _load_trace_report()
    trace = tmp_path / "trace.jsonl"
    with open(trace, "w") as f:
        for r in _synthetic_trace():
            f.write(json.dumps(r) + "\n")
    ledger = tmp_path / "ledger.json"
    ledger.write_text(json.dumps(
        {"uplink": {"total_bytes": 500}, "downlink": {"total_bytes": 1200},
         "overhead_up": 0, "overhead_down": 0}))
    replay = tmp_path / "replay.json"
    rc = tr.main([str(trace), "--ledger", str(ledger),
                  "--replay", str(replay), "--json"])
    assert rc == 0
    assert json.loads(replay.read_text())["rounds"]


# ---------------------------------------------------------------------------
# ledger overhead surfacing (the billed-but-dropped fix)
# ---------------------------------------------------------------------------


def test_ledger_roundtrips_overhead_and_defaults_old_snapshots():
    ch = InProcessChannel()
    ch.overhead_up += 123
    ch.overhead_down += 456
    led = ch.ledger()
    assert led["overhead_up"] == 123 and led["overhead_down"] == 456
    ch2 = InProcessChannel()
    ch2.restore_ledger(led)
    assert ch2.overhead_up == 123 and ch2.overhead_down == 456
    # a pre-PR9 ledger has no overhead keys: restore defaults them to 0
    old = {"uplink": led["uplink"], "downlink": led["downlink"]}
    ch3 = InProcessChannel()
    ch3.restore_ledger(old)
    assert ch3.overhead_up == 0 and ch3.overhead_down == 0


def test_live_result_surfaces_overhead():
    from benchmarks.fl_harness import ExperimentResult

    history = [{"round": 0, "losses": {0: 1.0, 1: 3.0}},
               {"round": 1, "losses": {}}]
    ledger = {"uplink": {"total_bytes": 1000},
              "downlink": {"total_bytes": 2000},
              "overhead_up": 50, "overhead_down": 60}
    res = ExperimentResult.from_live_run(
        "live", history, ledger, payload_floats=10.0, model_params=100,
        seconds=1.0)
    assert res.overhead_up_bytes == 50.0
    assert res.overhead_down_bytes == 60.0
    assert res.loss_curve == [2.0]
    assert res.wire_bytes == 500.0
