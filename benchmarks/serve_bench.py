"""Serving-layer benchmark: open-loop traffic through repro.serve_lp.

Emits one CSV row per traffic profile: us_per_call is mean end-to-end
request latency; derived packs throughput / p99 / padding / cache-hit
numbers.

The ``serve_shard_mesh`` profile runs the mesh flush path (shard_map,
uneven shards, cross-bucket fusing) on an underfull-heterogeneous
burst and additionally prints one ``JSON`` line with launch counts,
pad-waste fractions and the per-device row totals.  Run under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` to see the
multi-device layouts on a CPU host.

The burst profiles run under a ``repro.obs`` tracer, so their
``idle_frac`` numbers are *measured* from per-device ``device.solve``
spans (union of solve intervals per device over the traffic window)
rather than the old host-side ``device_idle_s_est`` gauge; the traced
rows carry the measurement in their ``JSON`` line.
"""
from __future__ import annotations

import json

from benchmarks.common import emit
from repro.serve_lp.bench import (BenchConfig, run_rpc_traffic,
                                  run_traffic, smoke_config)


def _shard_profile() -> BenchConfig:
    """Underfull-heterogeneous burst: requests spread over the full
    m-bucket ladder, so per-bucket occupancy stays well below
    max_batch and the fused/uneven machinery has real work to do."""
    cfg = BenchConfig(requests=240, rate=2000.0, m_min=8, m_max=1024,
                      max_batch=32, max_wait_s=0.005, check=8)
    cfg.open_loop = True
    return cfg


def run(full: bool = False) -> None:
    profiles = {"serve_smoke": smoke_config()}
    # Saturating burst through the pipelined loop: what overlapping
    # assembly with in-flight solves buys (inflight/overlap/idle come
    # from the gauges).
    burst = smoke_config()
    burst.open_loop = True
    burst.trace = True
    profiles["serve_burst_pipelined"] = burst
    if full:
        profiles["serve_open_loop"] = BenchConfig(
            requests=2000, rate=5000.0, m_max=1024, max_batch=128,
            max_wait_s=0.02)
        profiles["serve_kernel"] = BenchConfig(
            requests=256, rate=2000.0, m_max=256, max_batch=64,
            method="kernel", check=4)
    # The mesh path fuses underfull buckets into shared launches over
    # only the devices it needs.
    profiles["serve_shard_mesh"] = _shard_profile()
    for name, cfg in profiles.items():
        snap, _ = run_traffic(cfg, quiet=True)
        if name == "serve_shard_mesh":
            print("JSON " + json.dumps({
                "profile": name,
                "throughput_lps": round(snap["throughput_lps"], 1),
                "launches": snap["launches_total"],
                "flushes": snap["n_flushes"],
                "fused_flushes": snap["fused_flushes"],
                "fused_buckets": snap["fused_buckets"],
                "pad_waste_problems": round(
                    snap["padding_waste_problems"], 4),
                "pad_waste_cells": round(snap["padding_waste_cells"], 4),
                "rows_per_device": snap["rows_per_device"],
            }), flush=True)
        if "device_idle_frac" in snap:
            # Measured from device.solve spans (traced profile) —
            # supersedes the host-side estimate.
            idle = f"|idle_frac={snap['device_idle_frac']:.3f}"
            print("JSON " + json.dumps({
                "profile": name,
                "device_idle_frac": round(snap["device_idle_frac"], 4),
                "device_busy_s": round(snap["device_busy_s"], 4),
                "device_window_s": round(snap["device_window_s"], 4),
                "device_tracks": snap["device_tracks"],
                "trace_spans": snap["trace_spans"],
            }), flush=True)
        else:
            idle = f"|idle_s={snap['device_idle_s_est']:.3f}"
        emit(name, snap["latency_mean_ms"] / 1e3,
             f"lps={snap['throughput_lps']:.1f}"
             f"|p50ms={snap['latency_p50_ms']:.2f}"
             f"|p99ms={snap['latency_p99_ms']:.2f}"
             f"|waste_cells={snap['padding_waste_cells']:.3f}"
             f"|cache_hit={snap['cache']['hit_rate']:.3f}"
             f"|inflight_max={snap['inflight_max']}"
             f"|overlapped={snap['overlapped_dispatches']}"
             + idle +
             f"|launches={snap['launches_total']}"
             f"|fused={snap['fused_flushes']}")
    # Same smoke traffic through the HTTP front end: what the network
    # layer (parse + admission + loop hop) adds over in-process submit,
    # plus the overload-phase shed rate.
    rpc_cfg = smoke_config()
    rpc_cfg.rpc = True
    rep = run_rpc_traffic(rpc_cfg, quiet=True)
    c, o = rep["closed_loop"], rep["overload"]
    emit("serve_rpc_http", c["p50_ms"] / 1e3,
         f"rps={c['rps']:.1f}"
         f"|p50ms={c['p50_ms']:.2f}"
         f"|p99ms={c['p99_ms']:.2f}"
         f"|errors={c['errors']}"
         f"|shed_rate={o['shed_rate']:.3f}"
         f"|retry_after={int(o['retry_after_on_429'])}")
