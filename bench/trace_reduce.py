"""From a profiler trace to the numbers the benchmark reports.

``read_xplane`` takes the ``.xplane.pb`` that ``jax.profiler`` writes and
returns the device's operations and the harness's host spans, each as
(start_s, end_s, name) on the trace's one clock. ``reduce`` turns them into:

* ``busy_s``: the union of device-operation intervals in the traced window
  (averaged over the chips used);
* ``window_s``: the traced window, from the first to the last host span the
  harness wrote (``generate``, ``arrival_wait`` and those inside them);
* ``serving_s`` and ``busy_in_serving_s``: the union of ``generate`` spans,
  and the device-busy time inside them;
* ``device_ops``: the operations that took the most device time;
* ``idle_gaps``: the longest stretches with no operation on the device,
  each named by the innermost host span around it.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

OPS_LINE = "XLA Ops"
SERVING = "generate"
TOP = 10


def merge(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def intersect(a, b):
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals):
    return sum(e - s for s, e in intervals)


def _innermost(spans, t):
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def reduce(device_ops, host_spans, chips: int = 1) -> dict:
    """``device_ops``: {chip: [(start, end, name)]}; ``host_spans``:
    [(start, end, name)]."""
    if not host_spans:
        raise ValueError("the trace holds none of the harness's host spans")
    lo = min(s for s, _, _ in host_spans)
    hi = max(e for _, e, _ in host_spans)
    serving = merge((s, e) for s, e, n in host_spans if n == SERVING)
    busy = busy_serving = 0.0
    per_op = defaultdict(float)
    gaps = []
    for ops in device_ops.values():
        ivs = merge((max(s, lo), min(e, hi)) for s, e, _ in ops)
        busy += length(ivs)
        busy_serving += length(intersect(ivs, serving))
        for s, e, name in ops:
            if s < hi and e > lo:
                per_op[name] += min(e, hi) - max(s, lo)
        edges = [lo] + [t for iv in ivs for t in iv] + [hi]
        for k in range(0, len(edges), 2):
            s, e = edges[k], edges[k + 1]
            if e > s:
                gaps.append((e - s, _innermost(host_spans, (s + e) / 2)))
    n = max(len(device_ops), 1)
    gaps.sort(key=lambda g: -g[0])
    return {
        "chips_traced": len(device_ops),
        "busy_s": busy / n,
        "window_s": hi - lo,
        "serving_s": length(serving),
        "busy_in_serving_s": busy_serving / n,
        "device_ops": [[name, secs / n] for name, secs in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, secs] for secs, name in gaps[:TOP]],
    }


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str, span_names) -> tuple:
    """Device operations per chip and the named host spans in one trace.
    A device plane is ``/device:TPU:<n>``; its operations are the events of
    its ``XLA Ops`` line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                op_name(ev.name)) for ev in line.events)
            if ops:
                device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9, ev.name)
                             for ev in line.events if ev.name in span_names)
    return device_ops, spans


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]
