"""The ``BatchScheduler`` service, driven from one submitting thread.

Set-up makes every request of the run from the seed (vectorised by m,
``bench.generators.ladder``) and warms every executable the traffic can
reach: each m-bucket of the ladder at each flush size that has an
executable of its own, through the scheduler's own ``submit``/``flush``.

Traffic ``arrival``:

- ``poisson``: open loop at ``rate_per_s``.  The gaps are the
  exponential distribution's quantiles, in an order drawn from the
  seed, so every seed offers the same load.  Each request is timed from
  when it was due to when its future resolved; ``p50_ms``/``p99_ms``
  cover every request due in the window, a failed or unanswered one as
  a miss (infinite latency).
- ``saturate``: the thread submits as fast as ``submit`` returns, so
  dispatch backpressure sets the pace; ``lps_per_s`` counts the
  requests answered inside the window over its length.

After the window every request is waited for (a minute at most), and a
sample of the answered ones, drawn from the seed per m so the largest
are in it, is judged against the reference.
"""
from __future__ import annotations

import functools
import gc
import threading
import time

import numpy as np

from bench import generators, roofline, stats
from bench.device import describe
from bench.outcome import CompileCounter, Outcome, profiled

WAIT_AFTER_S = 60.0


def _requests(tr: dict, n: int, rng: np.random.Generator, key):
    """``n`` requests: m and kind drawn as whole-number shares of the
    ladder and the mix, in an order drawn from the seed; the LPs made
    from ``key``."""
    import jax
    ladder = [int(m) for m in tr["m_ladder"]]
    mix = [float(tr["mix"][k]) for k in generators.KINDS]
    ms = np.asarray(ladder)[generators.stratified(rng, n,
                                                  [1.0] * len(ladder))]
    kinds = generators.stratified(rng, n, mix)
    reqs = [None] * n
    for m in ladder:
        idx = np.nonzero(ms == m)[0]
        if idx.size == 0:
            continue
        A, b, c, _ = generators.ladder(jax.random.fold_in(key, m),
                                       kinds[idx], m)
        for j, i in enumerate(idx):
            reqs[i] = (A[j], b[j], c[j])
    return reqs, ms, kinds


def _due_times(rate: float, n: int, seconds: float,
               rng: np.random.Generator) -> np.ndarray:
    """Poisson arrivals at ``rate``: the n exponential quantile gaps in a
    seeded order, scaled so the last request is due at ``seconds``."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    rng.shuffle(gaps)
    t = np.cumsum(gaps)
    return t * (seconds / t[-1])


def _warm(sched, ladder, max_batch: int, key) -> int:
    """Run one flush of each size that can reach its own executable, for
    every m-bucket, each dispatched without waiting for the one before;
    returns the number of flushes.  The kernel's tile follows a flush's
    size in whole sublanes of 8 rows, and the padded batch climbs a
    ladder of tile multiples, so the multiples of 8 up to ``max_batch``
    reach every executable that a flush of 1 to ``max_batch`` rows (one
    bucket or several fused) can use; ``compiles_in_window`` shows any
    that were missed."""
    from repro.serve_lp.buckets import bucket_m
    buckets = {}
    for m in ladder:
        buckets.setdefault(bucket_m(m, base=sched.bucket_base), m)
    sizes = sorted({min(s, max_batch) for s in range(8, max_batch + 8, 8)})
    n = 0
    for bm, m in sorted(buckets.items()):
        A, b, c, _ = generators.ladder(key, np.zeros(max_batch, int), m)
        futs = []
        for size in sizes:
            futs += [sched.submit(A[i], b[i], c[i]) for i in range(size)]
            sched.flush()
            n += 1
        for fu in futs:
            fu.result(timeout=600)
    return n


def _build(cfg, tr, ctx):
    """The scheduler of the configuration, every executable warmed, its
    counters zeroed.  It runs without the program's span tracer, traced
    run or not: two spans a request add some 20-40 % to the host time of
    ``submit`` (on a CPU host), about what an open-loop mix at four
    fifths of the knee leaves spare, so a traced run could measure an
    overloaded host.  Its per-layer readings come from the scheduler's
    own counters instead."""
    from repro.serve_lp.metrics import ServeMetrics
    from repro.serve_lp.scheduler import BatchScheduler
    from repro.solver import SolverSpec
    sc = cfg["scheduler"]
    spec = SolverSpec(backend=cfg["backend"], M=float(cfg["M"]),
                      dtype=cfg["dtype"])
    sched = BatchScheduler(
        spec, devices=ctx.devices, max_batch=int(sc["max_batch"]),
        max_wait_s=float(sc["max_wait_s"]), pipeline=bool(sc["pipeline"]),
        max_inflight=int(sc["max_inflight"]), sharding=sc["sharding"],
        fuse=bool(sc["fuse"]))
    t = time.perf_counter()
    # The wait-trigger thread is not started yet, so no flush is cut
    # short of the size asked for.
    flushes = _warm(sched, tr["m_ladder"], int(sc["max_batch"]),
                    generators.jax_key(ctx.seed + 1))
    sched.metrics = ServeMetrics()
    ctx.log(f"served: {flushes} warm-up flushes, {len(sched.cache)} "
            f"executables, {len(ctx.devices)} chip(s) in "
            f"{time.perf_counter() - t:.3f} s")
    return sched


def run(cell, ctx) -> Outcome:
    from repro.serve_lp.metrics import ServeMetrics

    cfg, tr = cell.config, cell.traffic
    seconds = float(ctx.seconds)
    rng = np.random.default_rng([ctx.seed, 0x5E])
    saturate = tr["arrival"] == "saturate"
    if saturate:
        n = int(float(tr["requests_per_s_cap"]) * seconds)
    else:
        n = max(1, int(round(float(tr["rate_per_s"]) * seconds)))
    t = time.perf_counter()
    reqs, ms, kinds = _requests(tr, n, rng, generators.jax_key(ctx.seed))
    due = None if saturate else _due_times(float(tr["rate_per_s"]), n,
                                           seconds, rng)
    kept = ctx.keep.get("served") if ctx.keep is not None else None
    if kept is not None and not ctx.trace:
        sched = kept
    else:
        sched = _build(cfg, tr, ctx)
        if ctx.keep is not None and not ctx.trace:
            ctx.keep["served"] = sched
    sched.metrics = ServeMetrics()
    ctx.log(f"served: {n} requests made in {time.perf_counter() - t:.3f} s "
            f"with the scheduler's set-up")
    setup_s = time.perf_counter() - ctx.t_start

    # Only numbers are kept per request: a future is dropped once its
    # callback has stored its answer, so the window's live Python
    # objects stay few.
    done_t = np.full(n, np.nan)
    late = np.zeros(n)
    X = np.full((n, 2), np.nan, np.float32)
    F = np.zeros(n, bool)
    answered = np.zeros(n, bool)
    cv = threading.Condition()
    pending = [0]

    def on_done(i, fu):
        t = time.perf_counter()
        if not fu.cancelled() and fu.exception() is None:
            r = fu.result()
            X[i] = r.x
            F[i] = r.feasible
            answered[i] = True
        with cv:
            done_t[i] = t
            pending[0] -= 1
            cv.notify_all()

    # Set-up's objects (the traffic above all) are left out of the
    # garbage collector's passes in the window.
    gc.collect()
    gc.freeze()
    prof: dict = {}
    with CompileCounter() as compiles:
        with sched:
            with profiled(ctx.trace, prof):
                t0 = time.perf_counter()
                end = t0 + seconds
                sent = 0
                for i in range(n):
                    if saturate:
                        now = time.perf_counter()
                        if now >= end:
                            break
                        due_i = now
                    else:
                        due_i = t0 + due[i]
                        now = time.perf_counter()
                        if due_i > now:
                            time.sleep(due_i - now)
                    late[i] = time.perf_counter() - due_i
                    with cv:
                        pending[0] += 1
                    sched.submit(*reqs[i]).add_done_callback(
                        functools.partial(on_done, i))
                    sent += 1
                t_sent = time.perf_counter()
                # wait for every answer, a minute past the close at most
                deadline = max(end, t_sent) + WAIT_AFTER_S
                with cv:
                    cv.wait_for(lambda: pending[0] == 0,
                                timeout=max(0.0, deadline -
                                            time.perf_counter()))
                t1 = time.perf_counter()
    gc.unfreeze()
    if saturate and sent == n:
        ctx.log("generator ran out of requests before the window closed")
    device = describe(ctx.devices)
    snap = sched.metrics.snapshot(sched.cache.stats())

    with cv:
        answered = answered[:sent].copy()
        done_t = done_t[:sent].copy()
    failed = int(sent - answered.sum())
    e2e = {}
    if saturate:
        in_window = answered & (done_t <= end)
        e2e["lps_per_s"] = float(in_window.sum()) / seconds
    else:
        lat = np.where(answered, done_t - (t0 + due[:sent]), np.inf)
        lat_ms = [float(v) * 1e3 for v in lat]
        e2e["p50_ms"] = stats.percentile(lat_ms, 50)
        e2e["p99_ms"] = stats.percentile(lat_ms, 99)
        e2e["lps_per_s"] = float(answered.sum()) / (t1 - t0)
        # the backlog: median latency of each quarter of the requests,
        # in the order they were due
        quarters = [stats.percentile(q, 50) for q in
                    np.array_split(np.asarray(lat_ms), 4) if len(q)]
    lat_late = late[:sent]
    gen = {"offered": sent, "answered": int(answered.sum()),
           "failed": failed,
           "late_median_ms": 1e3 * float(np.median(lat_late)),
           "late_max_ms": 1e3 * float(np.max(lat_late, initial=0.0)),
           "compiles_in_window": compiles.n}
    ctx.log(f"generator: {gen}")
    if ctx.keep is None or ctx.trace:
        sched.close()
    del sched

    # The sample: per m, requests drawn from the seed among the answered.
    srng = np.random.default_rng([ctx.seed, 0xC4])
    per_m = int(cell.limits["sample_per_m"])
    sample = []
    valid = sum(int(ms[i]) for i in range(sent) if answered[i])
    for m in sorted(set(int(v) for v in ms[:sent])):
        idx = np.nonzero((ms[:sent] == m) & answered)[0]
        if idx.size == 0:
            continue
        pick = srng.choice(idx, min(per_m, idx.size), replace=False)
        A = np.stack([reqs[i][0] for i in pick])
        b = np.stack([reqs[i][1] for i in pick])
        c = np.stack([reqs[i][2] for i in pick])
        x, f = X[pick], F[pick]
        sample.append({"A": A, "b": b, "c": c,
                       "m_valid": np.full(len(pick), m), "x": x,
                       "feasible": f})
    counters = {
        "p50_ms_by_quarter": [] if saturate else quarters,
        "kernel_bytes": roofline.kernel_bytes(valid, int(answered.sum())),
        "n_flushes": snap["n_flushes"],
        "rows_per_device": snap["rows_per_device"],
        "padding_waste_cells": snap["padding_waste_cells"],
        "queue_wait_s": snap["histograms"]["queue_wait_seconds"]["sum"],
        "queue_waits": snap["histograms"]["queue_wait_seconds"]["count"],
        "assemble_s": snap["assemble_seconds"],
    }
    return Outcome(
        setup_s=setup_s, end_to_end=e2e, attempted=sent, failed=failed,
        sample=sample, device=device, counters=counters,
        trace_path=prof.get("trace_path"), generator=gen)
