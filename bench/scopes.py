"""The solver's stages and dispatch spans, from the profiler's trace of
the window.

The program puts every device op of a solve under one named scope,
``solver.prepare`` (cast, normalise, shuffle, pack), ``solver.pad``
(padding to the kernel's lane and tile multiples), ``solver.kernel``
(the LP kernel, or a dense backend) or ``solver.finish`` (slicing the
padding off, the flag cast, the objective), and marks each
``Solver.solve`` call on the host with a ``solver.solve`` span, from
entry until the asynchronous dispatch returns.  An op's scope path is
the ``op_name`` of its HLO metadata.  A v5e trace carries it as the
``tf_op`` stat of the op event's metadata (``jit(solve)/solver.pad/
scatter:``), which ``ProfileData`` does not show, so it is decoded from
the file here; the event is named by the op's HLO text, without
metadata.

``bench.trace_reduce`` keeps no scope paths, so the readers here read
the trace file again, once per run, and count only what lies inside
the ``bench.window`` annotation.  A trace of a program that names no
stage (no op under a ``solver.*`` scope) or no dispatch gives ``None``.
"""
from __future__ import annotations

import dataclasses
import re

from bench.trace_reduce import DEVICE_PREFIX, OPS_LINE, WINDOW

STAGES = ("solver.prepare", "solver.pad", "solver.kernel", "solver.finish")
DISPATCH = "solver.solve"


@dataclasses.dataclass
class Stages:
    stage_s: dict           # stage -> device seconds, summed over the chips
    unscoped_s: float       # device seconds of ops under no single stage
    spans_s: list           # each solver.solve span's host seconds


def stage_of(tf_op: str):
    """The one ``solver.*`` stage on an op's scope path, or ``None``
    where the path names none, or more than one (``;`` joins the names
    of ops XLA merged, ``:`` ends a name)."""
    found = {seg for seg in re.split(r"[/;:]", tf_op) if seg in STAGES}
    return found.pop() if len(found) == 1 else None


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of a protobuf message: an int for a
    varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(data: bytes) -> dict:
    """``{plane: {op event name: tf_op}}`` for the device planes of a
    serialized ``XSpace``: the ``tf_op`` stat of each event's metadata.
    Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``:
    XSpace.planes 1; XPlane name 2, event_metadata 4 and stat_metadata 5
    (maps: key 1, value 2); XEventMetadata name 2, stats 5;
    XStatMetadata name 2; XStat metadata_id 1, str_value 5."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = _text(pv)
            elif pf == 4:
                events.append(dict(_fields(pv)).get(2, b""))
            elif pf == 5:
                entry = dict(_fields(pv))
                stat = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = _text(stat.get(2, b""))
        if not name.startswith(DEVICE_PREFIX):
            continue
        table = out[name] = {}
        for ev in events:
            ev_name, tf_op = "", None
            for mf, mv in _fields(ev):
                if mf == 2:
                    ev_name = _text(mv)
                elif mf == 5:
                    stat = dict(_fields(mv))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        tf_op = _text(stat.get(5, b""))
            if tf_op is not None:
                table[ev_name] = tf_op
    return out


def _clip(s: int, e: int, w0: int, w1: int) -> float:
    return max(0, min(e, w1) - max(s, w0)) / 1e9


def read_trace(path: str) -> Stages:
    """Stage times and dispatch spans inside the window of the trace at
    ``path``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    scopes = op_scopes(data)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        raise ValueError("the trace has no bench.window annotation")
    w0, w1 = win[0][1], win[0][2]
    stage_s = dict.fromkeys(STAGES, 0.0)
    unscoped_s = 0.0
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        tf_op = scopes.get(plane.name, {})
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                d = _clip(e.start_ns, e.start_ns + e.duration_ns, w0, w1)
                if d <= 0:
                    continue
                stage = stage_of(tf_op.get(e.name, ""))
                if stage is None:
                    unscoped_s += d
                else:
                    stage_s[stage] += d
    spans = [_clip(s, e, w0, w1) for name, s, e in host if name == DISPATCH]
    return Stages(stage_s=stage_s, unscoped_s=unscoped_s,
                  spans_s=[d for d in spans if d > 0])


def read(ctx):
    """The run's :class:`Stages`, read once per trace and kept in the
    readers' shared ``ctx``; ``None`` without a trace."""
    path = ctx["outcome"].trace_path
    if not path:
        return None
    cache = ctx.setdefault(__name__, {})
    if path not in cache:
        cache[path] = read_trace(path)
    return cache[path]


def stage_ms(ctx, stage: str):
    """Device time per solve call of the ops under ``stage``; 0.0 where
    the window holds solver ops but none in it, ``None`` where it holds
    no solver op."""
    st = read(ctx)
    calls = ctx["counters"]["calls"]
    if st is None or not calls or not any(st.stage_s.values()):
        return None
    return 1e3 * st.stage_s[stage] / calls


def dispatch_ms(ctx):
    """Host time per ``solver.solve`` span in the window; ``None`` where
    there is none."""
    st = read(ctx)
    if st is None or not st.spans_s:
        return None
    return 1e3 * sum(st.spans_s) / len(st.spans_s)
