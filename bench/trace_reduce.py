"""From the profiler's trace of the window to per-layer numbers.

The trace (``.xplane.pb``, read with ``jax.profiler.ProfileData``) holds
one plane per TPU, ``/device:TPU:<id>``, whose ``XLA Ops`` line has one
event per operation run on the chip, and the host plane, where the
harness's ``bench.window`` annotation brackets the measured window.

- busy: the union of a chip's op intervals inside the window; the idle
  share is 1 - busy / window, averaged over the chips the cell holds;
- kernel time: the ops whose HLO is a Mosaic custom call
  (``custom_call_target="tpu_custom_call"``), the Pallas LP kernel; the
  kernel has no name of its own in the trace yet;
- the longest idle gaps, each labelled by the host event that overlaps
  it most (the program's own ``TraceAnnotation`` spans are host events
  of the trace too).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from bench import stats

WINDOW = "bench.window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
_TOP = 10
MIN_GAP_NS = 1000


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float               # mean over the chips of the cell
    ops_s: float                # op time summed over the chips
    kernel_s: float             # kernel time summed over the chips
    kernel_n: int
    n_devices: int
    op_seconds: dict            # label -> seconds summed over the chips
    gaps: list                  # [(label, seconds)], longest first

    def idle_pct(self):
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:_TOP]],
                "idle_gaps": [[k, v] for k, v in self.gaps[:_TOP]]}


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_label(hlo: str, module: str) -> str:
    """A short label of an XLA op event: the kernel, or module:op."""
    if KERNEL_MARK in hlo:
        return "lp_kernel (tpu_custom_call)"
    op = hlo.split(" = ", 1)[0].lstrip("%")
    return f"{module}:{op}" if module else op


def read_events(path: str):
    """``(devices, host)``: ``devices`` maps a TPU id to its
    ``[(label, start_ns, end_ns, is_kernel)]`` ops; ``host`` is every
    host event ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            lines = {ln.name: ln for ln in plane.lines}
            modules = []
            if MODULES_LINE in lines:
                modules = [(e.start_ns, e.start_ns + e.duration_ns,
                            _module_name(e.name))
                           for e in lines[MODULES_LINE].events]
            ops = []
            if OPS_LINE in lines:
                mi = 0
                for e in lines[OPS_LINE].events:
                    s = e.start_ns
                    while mi < len(modules) and modules[mi][1] < s:
                        mi += 1
                    mod = (modules[mi][2] if mi < len(modules)
                           and modules[mi][0] <= s else "")
                    ops.append((op_label(e.name, mod), s,
                                s + e.duration_ns, KERNEL_MARK in e.name))
            devices[dev] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    host.append((e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    return devices, host


def reduce(devices: dict, host: list, device_ids) -> Summary:
    """The summary of the window from events already read."""
    win = [h for h in host if h[0] == WINDOW]
    if not win:
        raise ValueError("the trace has no bench.window annotation")
    w0, w1 = win[0][1], win[0][2]
    window_s = (w1 - w0) / 1e9
    busy, ops_s, kern_s, kern_n = [], 0.0, 0.0, 0
    op_seconds = defaultdict(float)
    all_gaps = []
    for dev in device_ids:
        ivs = []
        for label, s, e, is_kernel in devices.get(dev, []):
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            ivs.append((s, e))
            d = (e - s) / 1e9
            ops_s += d
            op_seconds[label] += d
            if is_kernel:
                kern_s += d
                kern_n += 1
        busy.append(stats.union_length(ivs) / 1e9)
        all_gaps += [g for g in stats.gaps(ivs, w0, w1)[:_TOP]
                     if g[1] - g[0] >= MIN_GAP_NS]
    all_gaps.sort(key=lambda g: g[0] - g[1])
    labels = _labeller(host)
    gaps = [(labels(s, e), (e - s) / 1e9) for s, e in all_gaps[:_TOP]]
    n = max(1, len(device_ids))
    return Summary(window_s=window_s, busy_s=sum(busy) / n, ops_s=ops_s,
                   kernel_s=kern_s, kernel_n=kern_n, n_devices=n,
                   op_seconds=dict(op_seconds), gaps=gaps)


def _labeller(host):
    """``label(start, end)``: the host event that overlaps the stretch
    most (the window annotation aside)."""
    events = [h for h in host if h[0] != WINDOW]

    def best(cands, s, e):
        """The most overlap wins; of equal overlaps, the shortest event,
        which says most precisely what ran."""
        top, key = None, None
        for name, a, b in cands:
            ov = min(b, e) - max(a, s)
            if ov > 0 and (key is None or (ov, a - b) > key):
                top, key = name, (ov, a - b)
        return top

    def label(s, e):
        return best(events, s, e) or "host: nothing recorded"
    return label


def summarize(path: str, device_ids):
    devices, host = read_events(path)
    return reduce(devices, host, device_ids)


def read_idle(ctx):
    """The per-layer reader of the device's idle share: 1 - busy /
    window of the traced window, averaged over the chips the cell
    holds."""
    return ctx["trace"].idle_pct()
