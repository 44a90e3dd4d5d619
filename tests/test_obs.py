"""Observability-layer tests: repro.obs end to end.

Span plumbing is exercised against a *real* traced
:class:`BatchScheduler` (parent links, ordering invariants, fused
multi-bucket flush membership), the trace context rides a *real*
socket round-trip through the RPC front end, and the flight recorder
is triggered by an *injected* flush failure — not by calling
``trigger`` by hand.  Pure-structure pieces (ring wraparound, Chrome
trace schema, histogram exposition grammar, the JSON log formatter,
the snapshot race) are unit-tested directly.
"""
import io
import json
import logging
import threading
import time

import numpy as np
import pytest

from repro.obs import (FlightRecorder, NOOP_TRACER, SpanBuffer, Tracer,
                       check_span_chains, current_context, device_idle,
                       new_trace_context, parse_trace_header,
                       setup_logging, to_chrome_trace, use_context)
from repro.obs.export import validate_chrome_trace
from repro.obs.log import JsonFormatter, TextFormatter
from repro.obs.profiler import ProfileSession
from repro.obs.trace import (Span, flush_membership, span_index,
                             spans_for_trace)
from repro.serve_lp import BatchScheduler, ExecutableCache, SolverSpec
from repro.serve_lp.metrics import ServeMetrics
from repro.serve_lp.rpc import (make_frontend, render_metrics,
                                validate_exposition)
from repro.serve_lp.rpc.server import run_in_thread

SPEC = SolverSpec(backend="rgb", tile=16, chunk=0)


def _lp(seed=0, m=8):
    rng = np.random.default_rng(seed)
    xstar = rng.uniform(-10, 10, 2)
    theta = rng.uniform(0, 2 * np.pi, m)
    A = np.stack([np.cos(theta), np.sin(theta)], -1).astype(np.float32)
    b = (A @ xstar + rng.uniform(0.1, 3.0, m)).astype(np.float32)
    phi = rng.uniform(0, 2 * np.pi)
    c = np.array([np.cos(phi), np.sin(phi)], np.float32)
    return A, b, c


# -- trace context / header ------------------------------------------------

def test_parse_trace_header():
    ctx = new_trace_context()
    # bare trace id: parsed, fresh span id
    got = parse_trace_header(ctx.trace_id)
    assert got is not None and got.trace_id == ctx.trace_id
    # full "trace-span" form round-trips exactly
    got = parse_trace_header(ctx.header_value())
    assert (got.trace_id, got.span_id) == (ctx.trace_id, ctx.span_id)
    # malformed values are None, never an exception
    for bad in (None, "", "xyz", "0" * 31, "0" * 33,
                "0" * 32 + "-zz", "0" * 32 + "-" + "0" * 15,
                "0" * 32 + "-" + "0" * 16 + "-extra"):
        assert parse_trace_header(bad) is None, bad


def test_ring_wraparound():
    ring = SpanBuffer(capacity=4)
    for i in range(10):
        ring.append(Span("t" * 32, f"{i:016x}", None, "x",
                         t_start=float(i), t_end=float(i) + 0.5))
    assert len(ring) == 4
    assert ring.total == 10
    assert ring.dropped == 6
    snap = ring.snapshot()
    # oldest first, and only the newest 4 survive
    assert [s.t_start for s in snap] == [6.0, 7.0, 8.0, 9.0]
    ring.clear()
    assert len(ring) == 0 and ring.snapshot() == []


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s = tr.start_span("request", "a" * 32)
    assert s is None
    tr.end(s)                      # None accepted, no branching needed
    assert tr.record("device.solve", "a" * 32, None, 0.0, 1.0) is None
    assert tr.stats()["spans_recorded"] == 0
    assert tr.stats()["noop_calls"] == 3
    assert NOOP_TRACER.enabled is False


# -- scheduler span chains -------------------------------------------------

def test_scheduler_span_chain_invariants():
    tracer = Tracer(enabled=True)
    with BatchScheduler(SPEC, max_batch=4, max_wait_s=0.002,
                        tracer=tracer) as sched:
        futs = [sched.submit(*_lp(i)) for i in range(8)]
        for f in futs:
            assert f.result(timeout=60.0).feasible
    spans = tracer.spans()
    report = check_span_chains(spans)
    assert report["complete"] == 8
    assert report["problems"] == []
    by_id = span_index(spans)
    for s in spans:
        if s.name == "queue.wait":
            parent = by_id[s.parent_id]
            assert parent.name == "request"
            assert parent.trace_id == s.trace_id
            assert s.t_start >= parent.t_start
    # flush-plane spans all carry the flush label and a device track
    names = {s.name for s in spans}
    assert {"flush.assemble", "flush.dispatch", "device.solve",
            "flush.scatter"} <= names
    for s in spans:
        if s.name.startswith("flush.") or s.name == "device.solve":
            assert s.attrs.get("flush")
    idle = device_idle(spans)
    assert idle["window_s"] > 0.0
    assert 0.0 <= idle["idle_frac"] <= 1.0


def test_fused_flush_membership_routes_all_traces():
    tracer = Tracer(enabled=True)
    with BatchScheduler(SPEC, max_batch=64, max_wait_s=10.0,
                        tracer=tracer) as sched:
        # two m-buckets, both underfull -> one fused flush on close
        futs = ([sched.submit(*_lp(i, m=8)) for i in range(3)]
                + [sched.submit(*_lp(100 + i, m=64)) for i in range(3)])
        sched.flush()
        for f in futs:
            f.result(timeout=60.0)
    spans = tracer.spans()
    members = flush_membership(spans)
    fused = [name for name, tids in members.items() if len(tids) == 6]
    assert fused, f"no flush held all 6 traces: {members}"
    asm = next(s for s in spans if s.name == "flush.assemble"
               and s.attrs["flush"] == fused[0])
    assert asm.attrs["n_buckets"] >= 2
    # every member trace can pull the shared flush plane
    for tid in members[fused[0]]:
        mine = spans_for_trace(spans, tid)
        names = {s.name for s in mine}
        assert {"request", "queue.wait", "flush.assemble",
                "flush.dispatch", "device.solve",
                "flush.scatter"} <= names


def test_untraced_scheduler_records_nothing():
    with BatchScheduler(SPEC, max_batch=4, max_wait_s=0.002) as sched:
        futs = [sched.submit(*_lp(i)) for i in range(4)]
        for f in futs:
            f.result(timeout=60.0)
        stats = sched.tracer.stats()
    assert stats["enabled"] == 0
    assert stats["spans_recorded"] == 0
    assert stats["spans_started"] == 0


# -- RPC round-trip --------------------------------------------------------

def test_trace_id_roundtrip_over_socket():
    import http.client
    tracer = Tracer(enabled=True)
    f = make_frontend(SPEC, max_batch=4, max_wait_s=0.003,
                      tracer=tracer)
    port, stop = run_in_thread(f)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        A, b, c = _lp()
        body = json.dumps({"A": A.tolist(), "b": b.tolist(),
                           "c": c.tolist()})
        tid = "ab" * 16
        conn.request("POST", "/v1/solve", body,
                     {"X-Trace-Id": tid, "X-Deadline-Ms": "60000"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("X-Trace-Id") == tid
        resp.read()
        # absent header: the server mints one and echoes it
        conn.request("POST", "/v1/solve", body,
                     {"X-Deadline-Ms": "60000"})
        resp = conn.getresponse()
        minted = resp.getheader("X-Trace-Id")
        resp.read()
        assert minted and len(minted) == 32 and minted != tid
        # the trace is pullable as Chrome JSON scoped to the id
        conn.request("GET", f"/debug/trace?trace_id={tid}")
        resp = conn.getresponse()
        assert resp.status == 200
        obj = json.loads(resp.read())
        validate_chrome_trace(obj)
        assert obj["traceEvents"]
        # and as raw spans: rpc.handle -> admit/request parentage
        conn.request("GET", f"/debug/trace?trace_id={tid}&format=spans")
        sp = json.loads(conn.getresponse().read())["spans"]
        by_name = {}
        for s in sp:
            by_name.setdefault(s["name"], []).append(s)
        handle = by_name["rpc.handle"][0]
        assert by_name["admit"][0]["parent_id"] == handle["span_id"]
        assert by_name["request"][0]["parent_id"] == handle["span_id"]
        conn.close()
    finally:
        stop()
    chains = check_span_chains(tracer.spans())
    assert chains["problems"] == []


# -- flight recorder -------------------------------------------------------

class _BadExe:
    def dispatch(self, L, c, mv):
        return None

    def complete(self, handle):
        raise RuntimeError("injected device failure")


def test_flight_recorder_triggers_on_flush_failure(tmp_path):
    tracer = Tracer(enabled=True)
    rec = FlightRecorder(str(tmp_path), tracer=tracer,
                         min_interval_s=0.0)
    sched = BatchScheduler(SPEC, max_batch=4, max_wait_s=0.002,
                           tracer=tracer, recorder=rec)
    sched.cache = ExecutableCache(lambda spec: _BadExe())
    with sched:
        futs = [sched.submit(*_lp(i)) for i in range(4)]
        for f in futs:
            with pytest.raises(RuntimeError):
                f.result(timeout=60.0)
    assert rec.stats()["written"] >= 1
    names = rec.list_snapshots()
    assert names
    snap = rec.load_snapshot(names[0])
    assert snap["schema"] == "repro.obs.flight/1"
    assert snap["reason"].startswith("error:")
    assert snap["scheduler"]["n_devices"] >= 1
    assert any(s["name"] == "request" for s in snap["spans"])


def test_flight_recorder_debounce_prune_and_safety(tmp_path):
    rec = FlightRecorder(str(tmp_path), min_interval_s=3600.0,
                         max_snapshots=2)
    assert rec.trigger("one") is not None
    assert rec.trigger("two") is None          # debounced
    assert rec.stats()["suppressed"] == 1
    rec._t_last_write = -1e9                   # bypass debounce
    rec.trigger("two")
    rec._t_last_write = -1e9
    rec.trigger("three")
    assert len(rec.list_snapshots()) == 2      # pruned to max_snapshots
    assert rec.load_snapshot("../etc/passwd") is None
    assert rec.load_snapshot("nope.json") is None


def test_flight_recorder_p99_gate(tmp_path):
    rec = FlightRecorder(str(tmp_path), p99_threshold_s=0.1,
                         min_interval_s=0.0)
    rec.check_p99(0.05)
    assert rec.stats()["written"] == 0
    rec.check_p99(0.5)
    assert rec.stats()["written"] == 1
    assert "p99_threshold" in rec.list_snapshots()[0]
    snap = rec.load_snapshot(rec.list_snapshots()[0])
    assert snap["extra"]["p99_s"] == 0.5


# -- exporters -------------------------------------------------------------

def test_chrome_trace_schema():
    tr = Tracer(enabled=True)
    ctx = new_trace_context()
    r = tr.start_span("request", ctx.trace_id, ctx.span_id, bucket_m=8)
    q = tr.start_span("queue.wait", ctx.trace_id, r.span_id)
    tr.end(q)
    tr.end(r)
    tr.record("device.solve", ctx.trace_id, None,
              0.0, 1.0, flush="f1", devices=(0, 1), bucket_m=8)
    obj = to_chrome_trace(tr.spans())
    validate_chrome_trace(obj)
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert "X" in phases and "M" in phases
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"nope": 1})


def test_histogram_exposition_grammar():
    m = ServeMetrics()
    for i in range(40):
        m.record_latency(0.001 * (i + 1), trace_id=f"{i:032x}")
        m.record_queue_wait(0.0005 * (i + 1))
    m.record_flush(bucket_m=8, n_real=4, b_pad=16, sum_m=32,
                   solve_seconds=0.01, assemble_seconds=0.002,
                   reason="size", trace_id="ab" * 16)
    body = render_metrics(m.snapshot(), rpc=None, quotas=None,
                          trace=Tracer(enabled=True).stats())
    validate_exposition(body)
    assert 'le="+Inf"' in body
    assert "request_latency_seconds_bucket" in body
    assert '# {trace_id="' in body       # exemplar on a latency bucket
    assert "repro_serve_trace_enabled 1" in body
    # the validator actually enforces the histogram grammar
    with pytest.raises(ValueError):
        validate_exposition('# TYPE h histogram\nh_bucket{le="1"} 5\n'
                            'h_bucket{le="+Inf"} 3\nh_sum 1\nh_count 3\n')
    with pytest.raises(ValueError):
        validate_exposition('# TYPE h histogram\n'
                            'h_bucket{le="+Inf"} 5\nh_count 5\n')
    with pytest.raises(ValueError):
        validate_exposition('x_bucket{le="1"} 3 # malformed 1.0\n')


def test_snapshot_consistent_under_concurrent_records():
    m = ServeMetrics()
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            m.record_latency(0.001 * (i % 100 + 1))
            i += 1

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        deadline = time.perf_counter() + 0.5
        while time.perf_counter() < deadline:
            snap = m.snapshot()
            # percentiles come from the same locked copy: ordered and
            # inside the recorded value range
            assert snap["latency_p50_ms"] <= snap["latency_p99_ms"]
            assert 0.0 <= snap["latency_p99_ms"] <= 101.0
            h = snap["histograms"]["request_latency_seconds"]
            assert h["count"] == h["cumulative"][-1]
    finally:
        stop.set()
        for t in threads:
            t.join()


# -- structured logging ----------------------------------------------------

def test_json_log_formatter_binds_trace_context():
    stream = io.StringIO()
    logger = logging.getLogger("repro.test.obs.json")
    logger.propagate = False
    handler = setup_logging(fmt="json", stream=stream, logger=logger)
    try:
        with use_context(trace_id="ab" * 16, tenant="acme"):
            logger.info("flush %d done", 7, extra={"flush": "f-7"})
        logger.warning("outside")
    finally:
        logger.removeHandler(handler)
    lines = [json.loads(l) for l in stream.getvalue().splitlines()]
    assert lines[0]["msg"] == "flush 7 done"
    assert lines[0]["trace_id"] == "ab" * 16
    assert lines[0]["tenant"] == "acme"
    assert lines[0]["flush"] == "f-7"
    assert lines[0]["level"] == "INFO"
    assert "trace_id" not in lines[1]
    assert current_context() == {}


def test_profile_session_surfaces_profiler_errors(tmp_path):
    # A run asked for a trace must not exit 0 without one: the second
    # concurrent session's start_trace failure propagates.
    first = ProfileSession(str(tmp_path / "a"))
    assert first.start()
    try:
        with pytest.raises(RuntimeError):
            ProfileSession(str(tmp_path / "b")).start()
    finally:
        assert first.stop()
    assert not first.stop()


def test_text_formatter_and_setup_validation():
    rec = logging.LogRecord("x", logging.INFO, __file__, 1,
                            "hello", None, None)
    plain = TextFormatter().format(rec)
    assert "hello" in plain and "trace=" not in plain
    with use_context(trace_id="cd" * 16):
        bound = TextFormatter().format(rec)
    assert "trace=" + "cd" * 16 in bound
    with pytest.raises(ValueError):
        setup_logging(fmt="xml")
    # unserializable extras fall back via default=repr, never raise
    out = JsonFormatter().format(
        logging.LogRecord("x", logging.INFO, __file__, 1,
                          "obj %s", (object(),), None))
    assert json.loads(out)["level"] == "INFO"
