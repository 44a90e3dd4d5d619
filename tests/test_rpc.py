"""RPC front-end tests.

Admission control is exercised *without sockets* by driving
``LPFrontend.handle`` directly with synthetic :class:`Request` objects
(validation, quota exhaustion, deadline expiry, 429 backpressure, SLO
planning), plus one real-socket round-trip smoke over
``RpcServer``/``run_in_thread``.  Correctness criterion: an accepted
request's answer is bit-identical to a direct ``BatchScheduler.submit``
of the same LP.
"""
import asyncio
import json
import math
import time
from concurrent.futures import InvalidStateError

import jax
import numpy as np
import pytest

from repro.serve_lp import BatchScheduler, ExecutableCache, SolverSpec
from repro.serve_lp.metrics import ServeMetrics
from repro.serve_lp.rpc import (AdmissionPolicy, QuotaManager, Request,
                                RpcError, SLOController, TokenBucket,
                                check_backpressure, make_frontend,
                                parse_solve_payload, render_metrics,
                                run_in_thread, validate_exposition)
from repro.tune.table import TableEntry, TableKey, TuningTable

SPEC = SolverSpec(backend="rgb", tile=16, chunk=0)


def _lp(seed=0, m=3):
    rng = np.random.default_rng(seed)
    xstar = rng.uniform(-10, 10, 2)
    theta = rng.uniform(0, 2 * np.pi, m)
    A = np.stack([np.cos(theta), np.sin(theta)], -1).astype(np.float32)
    b = (A @ xstar + rng.uniform(0.1, 3.0, m)).astype(np.float32)
    phi = rng.uniform(0, 2 * np.pi)
    c = np.array([np.cos(phi), np.sin(phi)], np.float32)
    return A, b, c


def _problem_json(A, b, c, **extra):
    return {"A": A.tolist(), "b": b.tolist(), "c": c.tolist(), **extra}


def _post(frontend, obj, headers=None):
    req = Request("POST", "/v1/solve",
                  {k.lower(): v for k, v in (headers or {}).items()},
                  json.dumps(obj).encode())
    return asyncio.run(frontend.handle(req))


def _get(frontend, path):
    return asyncio.run(frontend.handle(Request("GET", path, {})))


def _body(resp):
    return json.loads(resp.body)


@pytest.fixture
def frontend():
    f = make_frontend(SPEC, max_batch=4, max_wait_s=0.003)
    f.start()
    yield f
    f.close()


@pytest.fixture
def frontend_slo():
    f = make_frontend(SPEC, max_batch=4, max_wait_s=0.003,
                      target_p99_s=0.05)
    f.start()
    yield f
    f.close()


def test_make_frontend_pins_devices():
    f = make_frontend(SPEC, devices=jax.devices()[:1])
    try:
        assert f.scheduler.n_devices == 1
    finally:
        f.close()


# -- token buckets --------------------------------------------------------

def test_token_bucket_refill_and_pricing():
    t = [0.0]
    bucket = TokenBucket(rate=10.0, burst=5.0, clock=lambda: t[0])
    assert bucket.try_take(5.0) == 0.0          # burst admitted
    retry = bucket.try_take(1.0)                # empty: priced rejection
    assert retry == pytest.approx(0.1)
    t[0] += 0.1                                 # refill exactly 1 token
    assert bucket.try_take(1.0) == 0.0
    assert bucket.try_take(math.inf if False else 6.0) == math.inf
    t[0] += 100.0                               # cap at burst
    assert bucket.tokens == pytest.approx(5.0)


def test_quota_manager_per_tenant_and_counters():
    t = [0.0]
    q = QuotaManager(rate=100.0, burst=10.0,
                     per_tenant={"vip": (1000.0, 100.0)},
                     clock=lambda: t[0])
    assert q.admit("vip", 50.0) == 0.0          # override honoured
    assert q.admit("anon", 50.0) == math.inf    # over default burst
    assert q.admit("anon", 10.0) == 0.0
    assert q.admit("anon", 1.0) > 0.0
    snap = q.snapshot()
    assert snap["anon"]["admitted"] == 10
    assert snap["anon"]["rejected"] == 51
    assert snap["vip"]["admitted"] == 50


# -- validation (socket-free, parse layer) --------------------------------

@pytest.mark.parametrize("body,status,code", [
    (b"{not json", 400, "bad_json"),
    (b'[1,2]', 400, "bad_request"),
    (json.dumps({"A": [[1, 0]], "b": [1]}).encode(), 422,
     "missing_field"),
    (json.dumps({"A": [[1, 0, 2]], "b": [1], "c": [1, 1]}).encode(),
     422, "bad_shape"),
    (json.dumps({"A": [], "b": [], "c": [1, 1]}).encode(), 422,
     "bad_shape"),
    (json.dumps({"A": [[1, 0]], "b": [1, 2], "c": [1, 1]}).encode(),
     422, "bad_shape"),
    (json.dumps({"A": [[1, 0]], "b": [1], "c": [1, 1, 1]}).encode(),
     422, "bad_shape"),
    (json.dumps({"A": [[1, "x"]], "b": [1], "c": [1, 1]}).encode(),
     422, "bad_dtype"),
    (json.dumps({"A": [[1, float("nan")]], "b": [1],
                 "c": [1, 1]}).encode(), 422, "nonfinite"),
    (json.dumps({"problems": []}).encode(), 422, "bad_request"),
])
def test_parse_rejections_typed(body, status, code):
    with pytest.raises(RpcError) as ei:
        parse_solve_payload(body, np.float32, AdmissionPolicy())
    assert ei.value.status == status
    assert ei.value.code == code


def test_parse_bounds():
    A, b, c = _lp(m=9)
    policy = AdmissionPolicy(m_max=8, batch_max=2)
    with pytest.raises(RpcError) as ei:
        parse_solve_payload(
            json.dumps(_problem_json(A, b, c)).encode(), np.float32,
            policy)
    assert (ei.value.status, ei.value.code) == (422, "m_out_of_bounds")
    A, b, c = _lp(m=3)
    probs = {"problems": [_problem_json(A, b, c)] * 3}
    with pytest.raises(RpcError) as ei:
        parse_solve_payload(json.dumps(probs).encode(), np.float32,
                            policy)
    assert (ei.value.status, ei.value.code) == (413, "batch_too_large")
    with pytest.raises(RpcError) as ei:
        parse_solve_payload(b"x" * 100, np.float32,
                            AdmissionPolicy(body_max_bytes=10))
    assert (ei.value.status, ei.value.code) == (413, "body_too_large")


def test_validation_never_touches_scheduler(frontend):
    resp = _post(frontend, {"A": [[1, 0, 3]], "b": [1], "c": [1, 1]})
    assert resp.status == 422
    assert frontend.scheduler.pending() == 0
    assert frontend.scheduler.metrics.n_solved == 0
    assert frontend.counters.snapshot()["lps_accepted"] == 0


# -- solving through the handler ------------------------------------------

def test_single_and_batch_solve_bit_identical_to_direct(frontend):
    lps = [_lp(seed=s, m=m) for s, m in
           [(1, 3), (2, 5), (3, 8), (4, 3)]]
    # through the RPC handler (batch form)
    resp = _post(frontend, {"problems":
                            [_problem_json(*lp) for lp in lps]})
    assert resp.status == 200
    results = _body(resp)["results"]
    assert len(results) == len(lps)
    # direct submit of the same arrays with the same spec
    with BatchScheduler(SPEC, max_batch=len(lps)) as direct:
        futs = [direct.submit(*lp) for lp in lps]
        direct.flush()
        want = [f.result(timeout=60) for f in futs]
    for got, ref in zip(results, want):
        assert got["feasible"] == bool(ref.feasible)
        np.testing.assert_array_equal(
            np.asarray(got["x"], np.float32), ref.x)
    # single form mirrors batch form
    resp = _post(frontend, _problem_json(*lps[0]))
    assert resp.status == 200
    np.testing.assert_array_equal(
        np.asarray(_body(resp)["result"]["x"], np.float32), want[0].x)


def test_internal_errors_do_not_leak_reprs(frontend, monkeypatch):
    def _boom(*a, **k):
        raise RuntimeError("secret-internal-detail /opt/private/path")

    monkeypatch.setattr(frontend.quotas, "admit", _boom)
    resp = _post(frontend, _problem_json(*_lp()))
    assert resp.status == 500
    assert _body(resp)["error"]["code"] == "internal"
    assert "secret-internal-detail" not in resp.body.decode()
    # the repr lands in the server-side error counter instead
    assert frontend.scheduler.metrics.errors.get("rpc_internal") == 1


def test_oversized_lines_get_400_not_connection_drop():
    # StreamReader.readline signals over-limit lines as ValueError;
    # both the request line and header lines must map it to a 400.
    from repro.serve_lp.rpc.server import _read_request

    def _parse(payload):
        async def _run():
            reader = asyncio.StreamReader(limit=1024)
            reader.feed_data(payload)
            reader.feed_eof()
            return await _read_request(reader, body_max=1 << 20)
        return asyncio.run(_run())

    with pytest.raises(RpcError) as ei:
        _parse(b"GET /" + b"x" * 4096 + b" HTTP/1.1\r\n\r\n")
    assert (ei.value.status, ei.value.code) == (400, "bad_request")
    with pytest.raises(RpcError) as ei:
        _parse(b"POST /v1/solve HTTP/1.1\r\nx-big: " + b"y" * 4096
               + b"\r\n\r\n")
    assert (ei.value.status, ei.value.code) == (400, "bad_request")


def test_method_and_route_errors(frontend):
    resp = asyncio.run(frontend.handle(
        Request("GET", "/v1/solve", {})))
    assert resp.status == 405
    resp = asyncio.run(frontend.handle(Request("GET", "/nope", {})))
    assert resp.status == 404
    snap = frontend.counters.snapshot()
    assert snap["requests"][("solve", 405)] == 1
    assert snap["requests"][("other", 404)] == 1


# -- quotas through the handler -------------------------------------------

def test_quota_exhaustion_429_then_refill():
    t = [0.0]
    f = make_frontend(
        SPEC, max_batch=1, max_wait_s=0.003,
        quotas=QuotaManager(rate=100.0, burst=2.0, clock=lambda: t[0]))
    f.start()
    try:
        prob = _problem_json(*_lp())
        assert _post(f, prob, {"X-Tenant": "t1"}).status == 200
        assert _post(f, prob, {"X-Tenant": "t1"}).status == 200
        resp = _post(f, prob, {"X-Tenant": "t1"})
        assert resp.status == 429
        err = _body(resp)["error"]
        assert err["code"] == "quota_exhausted"
        assert resp.headers["Retry-After"] == "1"
        assert err["retry_after_ms"] == pytest.approx(10.0, abs=1.0)
        # an unrelated tenant is unaffected
        assert _post(f, prob, {"X-Tenant": "t2"}).status == 200
        # refill admits t1 again
        t[0] += 0.05
        assert _post(f, prob, {"X-Tenant": "t1"}).status == 200
        assert f.counters.snapshot()["shed"]["quota_exhausted"] == 1
    finally:
        f.close()


def test_batch_over_burst_is_413_not_retryable():
    f = make_frontend(SPEC, max_batch=8, max_wait_s=0.003,
                      quotas=QuotaManager(rate=100.0, burst=2.0))
    f.start()
    try:
        probs = {"problems": [_problem_json(*_lp())] * 3}
        resp = _post(f, probs)
        assert resp.status == 413
        assert _body(resp)["error"]["code"] == "batch_exceeds_burst"
        assert "Retry-After" not in resp.headers
    finally:
        f.close()


# -- deadlines -------------------------------------------------------------

def test_bad_deadline_rejected(frontend):
    resp = _post(frontend, _problem_json(*_lp()),
                 {"X-Deadline-Ms": "bogus"})
    assert resp.status == 400
    assert _body(resp)["error"]["code"] == "bad_deadline"
    resp = _post(frontend, _problem_json(*_lp()),
                 {"X-Deadline-Ms": "-5"})
    assert resp.status == 400


def test_deadline_expiry_cancels_instead_of_solving():
    # A scheduler that will never flush on its own: the request sits
    # queued until the deadline fires, the handler answers 504, and the
    # cancelled work is dropped at the next flush instead of solved.
    f = make_frontend(SPEC, max_batch=4096, max_wait_s=30.0)
    f.start()
    try:
        t0 = time.perf_counter()
        resp = _post(f, _problem_json(*_lp()),
                     {"X-Deadline-Ms": "40"})
        waited = time.perf_counter() - t0
        assert resp.status == 504
        assert _body(resp)["error"]["code"] == "deadline_exceeded"
        assert waited < 5.0          # did not wait for the 30s timer
        assert f.counters.snapshot()["shed"]["deadline_exceeded"] == 1
        sched = f.scheduler
        assert sched.pending() == 1  # still queued, future cancelled
        sched.flush()                # drops the cancelled request
        sched.drain()
        assert sched.metrics.n_solved == 0
        assert sched.metrics.n_flushes == 0
    finally:
        f.close()


def test_deadline_header_wins_over_body(frontend):
    # generous header, absurd body field: header must win -> solves
    resp = _post(frontend, _problem_json(*_lp(), deadline_ms=0.001),
                 {"X-Deadline-Ms": "60000"})
    assert resp.status == 200


# -- backpressure ----------------------------------------------------------

class _StubSched:
    def __init__(self, pending=0, inflight=0, max_inflight=2, age=0.0):
        self._pending, self._age = pending, age
        self.inflight, self.max_inflight = inflight, max_inflight

    def pending(self):
        return self._pending

    def queue_age_s(self, now=None):
        return self._age


def test_backpressure_depth_and_age_signals():
    policy = AdmissionPolicy(max_pending=10, max_queue_age_s=0.2)
    # healthy: deep queue but device not saturated
    check_backpressure(_StubSched(pending=50, inflight=1), policy)
    # depth: queue deep AND in-flight at bound
    with pytest.raises(RpcError) as ei:
        check_backpressure(_StubSched(pending=10, inflight=2), policy)
    assert ei.value.status == 429
    assert ei.value.retry_after_s is not None
    # age: oldest request waited too long
    with pytest.raises(RpcError):
        check_backpressure(_StubSched(age=0.5), policy)


def test_backpressure_sheds_through_handler():
    f = make_frontend(SPEC, max_batch=4096, max_wait_s=30.0,
                      policy=AdmissionPolicy(max_queue_age_s=0.0))
    f.start()
    try:
        # age an (unflushable) queued request past the 0.0s bound
        f.scheduler.submit(*_lp())
        time.sleep(0.01)
        resp = _post(f, _problem_json(*_lp()))
        assert resp.status == 429
        assert _body(resp)["error"]["code"] == "overloaded"
        assert "Retry-After" in resp.headers
        assert f.counters.snapshot()["shed"]["overloaded"] == 1
        assert f.scheduler.pending() == 1   # shed was never queued
    finally:
        f.close()


def test_shed_request_costs_no_quota_tokens():
    # Backpressure runs before quota: a 429-shed request must not also
    # deduct the tenant's token-bucket balance.
    quotas = QuotaManager(rate=100.0, burst=10.0)
    f = make_frontend(SPEC, max_batch=4096, max_wait_s=30.0,
                      policy=AdmissionPolicy(max_queue_age_s=0.0),
                      quotas=quotas)
    f.start()
    try:
        f.scheduler.submit(*_lp())
        time.sleep(0.01)
        resp = _post(f, _problem_json(*_lp()), {"X-Tenant": "t1"})
        assert resp.status == 429
        snap = quotas.snapshot()
        assert "t1" not in snap or (snap["t1"]["admitted"] == 0
                                    and snap["t1"]["rejected"] == 0)
    finally:
        f.close()


# -- SLO controller --------------------------------------------------------

def _measured_table(us_per_lp, m_bucket=8, tile=16):
    return TuningTable([TableEntry(
        key=TableKey(device_kind="cpu", backend="rgb",
                     dtype="float32", m_bucket=m_bucket,
                     batch_bucket=0),
        tile=tile, chunk=0, us_per_lp=us_per_lp, source="measured")])


def test_slo_derives_limits_from_measured_latency():
    # one pinned device so the flush-time arithmetic below stays exact
    # whatever XLA_FLAGS forced-device count the suite runs under
    sched = BatchScheduler(SPEC, max_batch=256, max_wait_s=0.005,
                           devices=jax.devices()[:1])
    slo = SLOController(0.05, table=_measured_table(50.0),
                        device_kind="cpu")
    slo.install(sched, m_max=8)
    plan = slo.plans()[8]
    assert plan.source == "measured"
    # est_flush = 50us * 256 = 12.8ms; wait = 50 - 2*12.8 = 24.4ms —
    # the acceptance contract: derived max_wait_s differs from the
    # 5ms default when a measured table is active.
    assert plan.est_flush_s == pytest.approx(12.8e-3)
    assert plan.max_wait_s == pytest.approx(24.4e-3)
    assert plan.max_wait_s != 0.005
    assert plan.max_batch == 256
    # and the scheduler consults the installed plan per bucket
    assert sched._limits_for(8) == (plan.max_batch, plan.max_wait_s)


def test_slo_caps_batch_for_slow_buckets():
    sched = BatchScheduler(SPEC, max_batch=256, max_wait_s=0.005,
                           devices=jax.devices()[:1])
    slo = SLOController(0.05, table=_measured_table(500.0),
                        device_kind="cpu")
    slo.install(sched, m_max=8)
    plan = slo.plans()[8]
    # 500us/LP: a 256-batch flush is 128ms >> the 25ms service budget;
    # halving lands on 32 (16ms).
    assert plan.max_batch == 32
    assert plan.est_flush_s == pytest.approx(16e-3)
    assert plan.max_wait_s == pytest.approx(0.05 - 32e-3)


def test_slo_defaults_without_measurements():
    sched = BatchScheduler(SPEC, max_batch=64, max_wait_s=0.004)
    slo = SLOController(0.05, table=TuningTable(), device_kind="cpu")
    slo.install(sched, m_max=16)
    for plan in slo.plans().values():
        assert plan.source == "default"
        assert plan.max_batch == 64
        assert plan.max_wait_s == 0.004
    assert sched._limits_for(8) == (64, 0.004)


def test_slo_ignores_heuristic_seeded_entries():
    table = TuningTable([TableEntry(
        key=TableKey(device_kind="cpu", backend="rgb",
                     dtype="float32", m_bucket=8, batch_bucket=0),
        tile=16, chunk=0, us_per_lp=1e9, source="heuristic-seed")])
    sched = BatchScheduler(SPEC, max_batch=64, max_wait_s=0.004)
    slo = SLOController(0.05, table=table, device_kind="cpu")
    slo.install(sched, m_max=8)
    assert slo.plans()[8].source == "default"


def test_slo_allow_fuse_veto_from_next_rung_timing():
    """Fusing solves a bucket at the next ladder rung's m_pad; when the
    measured timing there blows the flush-service budget, the plan
    vetoes fusing and the installed policy keeps the bucket out of
    fused units — while slower-but-unmeasured rungs stay fusable."""
    table = TuningTable([
        TableEntry(key=TableKey(device_kind="cpu", backend="rgb",
                                dtype="float32", m_bucket=8,
                                batch_bucket=0),
                   tile=16, chunk=0, us_per_lp=50.0, source="measured"),
        # the m=16 rung is measured catastrophically slow: a fused
        # flush carrying bucket-8 work at m_pad=16 would blow the p99
        TableEntry(key=TableKey(device_kind="cpu", backend="rgb",
                                dtype="float32", m_bucket=16,
                                batch_bucket=0),
                   tile=16, chunk=0, us_per_lp=1e5, source="measured"),
    ])
    sched = BatchScheduler(SPEC, max_batch=256, max_wait_s=0.005,
                           devices=jax.devices()[:1])
    slo = SLOController(0.05, table=table, device_kind="cpu")
    slo.install(sched, m_max=16)
    plans = slo.plans()
    assert plans[8].allow_fuse is False
    # bucket 16's own next rung (32) has no measurement: fusable
    assert plans[16].allow_fuse is True
    assert sched._fuse_ok(8) is False
    assert sched._fuse_ok(16) is True


def test_slo_flush_estimate_divides_by_used_devices_only():
    """The mesh-aware service model: a full flush spreads over all
    devices, so its estimated service time shrinks with the device
    count — which loosens the batch cap relative to one device."""
    sched = BatchScheduler(SPEC, max_batch=256, max_wait_s=0.005,
                           devices=jax.devices()[:1])
    # planning-only stand-in for a 4-device mesh (no executables are
    # built through this scheduler)
    sched._devices = sched._devices * 4
    slo = SLOController(0.05, table=_measured_table(50.0),
                        device_kind="cpu")
    plan = slo.plan_for(sched, 8)
    # 50us/LP * 256 rows over min(4, 256/16)=4 used devices = 3.2ms
    assert plan.est_flush_s == pytest.approx(3.2e-3)
    assert plan.max_batch == 256


def test_render_metrics_slo_and_sharding_families():
    """The scrape exposes the SLO per-bucket plans and the fused/launch
    counters as labelled families."""
    from repro.serve_lp.rpc.slo import BucketPlan
    m = ServeMetrics()
    m.record_flush(n_real=3, b_pad=16, bucket_m=16, sum_m=30,
                   solve_seconds=0.01, reason="fused", n_buckets=2,
                   launches=2, shards=(8, 8))
    snap = m.snapshot()
    plans = {8: BucketPlan(bucket_m=8, max_batch=32, max_wait_s=0.01,
                           est_flush_s=0.004, source="measured",
                           allow_fuse=False),
             16: BucketPlan(bucket_m=16, max_batch=64, max_wait_s=0.02,
                            est_flush_s=None, source="default")}
    text = render_metrics(snap, slo=plans)
    validate_exposition(text)
    assert ('repro_serve_slo_bucket_max_batch{bucket_m="8",'
            'source="measured"} 32') in text
    assert ('repro_serve_slo_bucket_max_wait_seconds{bucket_m="16",'
            'source="default"} 0.02') in text
    assert ('repro_serve_slo_bucket_allow_fuse{bucket_m="8",'
            'source="measured"} 0') in text
    assert ('repro_serve_slo_bucket_allow_fuse{bucket_m="16",'
            'source="default"} 1') in text
    # est_flush renders 0 (not NaN) when no measured entry exists
    assert ('repro_serve_slo_bucket_est_flush_seconds{bucket_m="16",'
            'source="default"} 0') in text
    assert "repro_serve_launches_total 2" in text
    assert "repro_serve_fused_flushes_total 1" in text
    assert "repro_serve_fused_buckets_total 2" in text
    assert 'repro_serve_device_rows_total{device="0"} 8' in text
    assert 'repro_serve_device_rows_total{device="1"} 8' in text


def test_metrics_endpoint_exposes_slo_plans(frontend_slo):
    """An SLO-enabled front end publishes its per-bucket plans on
    /metrics after traffic has touched a bucket."""
    _post(frontend_slo, _problem_json(*_lp()))
    resp = _get(frontend_slo, "/metrics")
    assert resp.status == 200
    text = resp.body.decode()
    validate_exposition(text)
    assert "repro_serve_slo_bucket_max_batch{" in text
    assert "repro_serve_slo_bucket_allow_fuse{" in text


def test_scheduler_per_bucket_policy_drives_size_trigger():
    with BatchScheduler(SPEC, max_batch=64, max_wait_s=10.0) as sched:
        sched.set_bucket_policy(lambda bm: (2, 10.0))
        f1 = sched.submit(*_lp(seed=1))
        f2 = sched.submit(*_lp(seed=2))   # second hits the per-bucket cap
        r1, r2 = f1.result(timeout=60), f2.result(timeout=60)
        assert r1.batch_size == 2 and r2.batch_size == 2
        assert sched.metrics.flush_reasons.get("size") == 1


# -- prometheus exposition -------------------------------------------------

def test_fresh_metrics_render_nan_free():
    # empty-reservoir guard: a scrape before any traffic must be finite
    m = ServeMetrics()
    assert m.percentile(99.0) == 0.0
    snap = m.snapshot({"hits": 0, "misses": 0, "size": 0,
                       "hit_rate": 0.0})
    text = render_metrics(snap, rpc={"requests": {}, "shed": {},
                                     "inprogress": 0,
                                     "lps_accepted": 0},
                          quotas={})
    validate_exposition(text)
    samples = [ln for ln in text.splitlines()
               if ln and not ln.startswith("#")]
    assert samples and all(
        math.isfinite(float(ln.rsplit(" ", 1)[1])) for ln in samples)


def test_metrics_endpoint_exposes_scheduler_and_rpc_counters(frontend):
    resp = _get(frontend, "/metrics")         # pre-traffic scrape
    assert resp.status == 200
    validate_exposition(resp.body.decode())
    _post(frontend, _problem_json(*_lp()))
    _post(frontend, {"A": "garbage", "b": [1], "c": [1, 1]})
    resp = _get(frontend, "/metrics")
    text = resp.body.decode()
    validate_exposition(text)
    assert resp.content_type.startswith("text/plain; version=0.0.4")
    assert "repro_serve_solved_total 1" in text
    assert ('repro_serve_rpc_requests_total{code="200",'
            'endpoint="solve"} 1') in text
    assert ('repro_serve_rpc_requests_total{code="422",'
            'endpoint="solve"} 1') in text
    assert 'repro_serve_rpc_quota_admitted_total{tenant="anonymous"} 1' \
        in text


def test_health_and_ready(frontend):
    assert _get(frontend, "/healthz").status == 200
    assert _get(frontend, "/readyz").status == 200
    frontend.close()
    assert _get(frontend, "/healthz").status == 200   # alive, draining
    assert _get(frontend, "/readyz").status == 503


# -- drain() satellite -----------------------------------------------------

class _SlowExec:
    def __init__(self, delay):
        self.delay = delay

    def dispatch(self, L, c, mv):
        return (np.zeros((L.shape[0], 2), np.float32),
                np.zeros((L.shape[0],), bool))

    def complete(self, handle):
        time.sleep(self.delay)
        return handle


def test_drain_returns_false_on_timeout_then_true():
    sched = BatchScheduler(SPEC, max_batch=2, max_wait_s=10.0)
    sched.cache = ExecutableCache(lambda spec: _SlowExec(0.4))
    futs = [sched.submit(*_lp(seed=s)) for s in (1, 2)]  # size flush
    assert sched.drain(timeout=0.05) is False   # still completing
    assert sched.drain(timeout=30.0) is True
    for f in futs:
        assert f.result(timeout=1).feasible is False
    sched.close()


def test_stop_records_drain_timeout(monkeypatch):
    sched = BatchScheduler(SPEC, max_batch=8, max_wait_s=10.0)
    monkeypatch.setattr(sched, "drain", lambda timeout=600.0: False)
    with pytest.warns(RuntimeWarning, match="timed out draining"):
        sched.stop()
    assert sched.metrics.errors.get("drain_timeout") == 1


def test_cancelled_future_skipped_at_scatter():
    with BatchScheduler(SPEC, max_batch=64, max_wait_s=10.0) as sched:
        f1 = sched.submit(*_lp(seed=1))
        f2 = sched.submit(*_lp(seed=2))
        assert f1.cancel()
        sched.flush()
        sched.drain()
        assert f2.result(timeout=60).feasible
        assert f1.cancelled()
        assert not sched.metrics.errors


def test_flush_claims_futures_so_cancel_cannot_race_completion():
    # Once a flush picks a request up, the deadline machinery's
    # cancel() must lose cleanly (return False) instead of racing the
    # completion scatter into InvalidStateError.
    sched = BatchScheduler(SPEC, max_batch=2, max_wait_s=10.0)
    sched.cache = ExecutableCache(lambda spec: _SlowExec(0.3))
    try:
        f1 = sched.submit(*_lp(seed=1))
        f2 = sched.submit(*_lp(seed=2))   # size flush: both claimed
        assert f1.cancel() is False       # too late — the flush owns it
        assert f1.result(timeout=30) is not None
        assert f2.result(timeout=30) is not None
        assert not sched.metrics.errors
    finally:
        sched.close()


class _RacedFuture:
    """done() still says pending, but a cross-thread cancel already
    won — the window the done() pre-check cannot close."""

    def done(self):
        return False

    def set_result(self, value):
        raise InvalidStateError("cancelled")

    def set_exception(self, exc):
        raise InvalidStateError("cancelled")


def test_settle_tolerates_lost_cancel_race():
    from repro.serve_lp.scheduler import (_try_set_exception,
                                          _try_set_result)
    assert _try_set_result(_RacedFuture(), 1) is False
    assert _try_set_exception(_RacedFuture(), ValueError("x")) is False


# -- real-socket smoke -----------------------------------------------------

def test_socket_roundtrip_smoke():
    import http.client
    f = make_frontend(SPEC, max_batch=4, max_wait_s=0.003)
    port, stop = run_in_thread(f)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        A, b, c = _lp()
        body = json.dumps(_problem_json(A, b, c))
        # keep-alive: several requests over one connection
        conn.request("POST", "/v1/solve", body,
                     {"X-Tenant": "sock", "X-Deadline-Ms": "60000"})
        resp = conn.getresponse()
        assert resp.status == 200
        got = json.loads(resp.read())["result"]
        with BatchScheduler(SPEC, max_batch=1) as direct:
            ref = direct.submit(A, b, c).result(timeout=60)
        np.testing.assert_array_equal(
            np.asarray(got["x"], np.float32), ref.x)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
        assert resp.status == 200
        validate_exposition(text)
        assert 'tenant="sock"' in text
        conn.request("GET", "/healthz")
        assert conn.getresponse().read() == b"ok\n"
        conn.request("POST", "/v1/solve", "{bad",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
        conn.close()
    finally:
        stop()
