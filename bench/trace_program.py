"""From a profiler trace to what the serving engine wrote into it.

``RealExecutionEngine`` with a recorder attached writes ``engine.*`` host
spans (``repro.serving.realexec``), and its jitted programs are named
(``jit_prefill_miss``, ``jit_prefill_hit``, ``jit_decode_step``), so the
device's ``XLA Modules`` line says which program ran. ``read_xplane`` takes
the ``.xplane.pb`` that ``jax.profiler`` writes and returns, on the trace's
one clock, the modules and operations of each device plane and the host
spans with their metadata. ``reduce`` turns them into:

* ``programs``: device seconds and runs per program, within the traced
  window (averaged over the chips);
* ``idle_in_serving``: the seconds inside serving (``generate`` or
  ``engine.generate`` spans) with no operation on the device, summed by the
  innermost ``engine.*`` span over each instant (``no engine span`` where
  there is none);
* ``decode``: the decode steps split into the decode program's device time
  and the rest of the ``engine.decode`` spans (host dispatch, input
  uploads, the argmax and the host read of each token), per step;
* ``clock_lag_ms``: the lag from each ``engine.decode_step`` span's start
  to the start of its ``jit_decode_step`` module (dispatch latency plus the
  offset between the host's and the device's clocks), minimum and median.

Each ``engine.decode_step`` span dispatches exactly one ``jit_decode_step``
run, so they are paired in order when their counts agree (whatever the
offset between the two clocks), and otherwise each span with the run that
starts nearest to it. A program's whole run counts towards the step it
was paired with, even where it crosses a span's edge.

``store_payload_ratio`` reads the recorder's ``store_bytes`` events:
what the store's payloads hold on the device over what it charges.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

from bench.trace_reduce import OPS_LINE, intersect, length, merge, op_name

MODULES_LINE = "XLA Modules"
SERVING = ("generate", "engine.generate")
HARNESS = ("generate", "arrival_wait")
DECODE_PROGRAM = "jit_decode_step"
NO_SPAN = "no engine span"
TOP = 10


def module_name(event_name: str) -> str:
    """``jit_decode_step(1977554265506175217)`` -> ``jit_decode_step``."""
    return event_name.split("(", 1)[0]


def read_xplane(path: str, names=HARNESS) -> tuple:
    """Per device plane (``/device:TPU:<n>``) its modules and its
    operations, as {plane: [(start_s, end_s, name)]}, and the host spans
    of the engine and those in ``names`` as [(start_s, end_s, name,
    metadata)]."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules, ops, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                into, name = {MODULES_LINE: (modules, module_name),
                              OPS_LINE: (ops, op_name)}.get(
                                  line.name, (None, None))
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9,
                         name(ev.name)) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("engine.") or ev.name in names:
                        spans.append((ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9,
                                      ev.name, dict(ev.stats)))
    return modules, ops, spans


def innermost(spans):
    """The union of nested (start, end, name, ...) spans as sorted disjoint
    (start, end, name) pieces, each named by the innermost span over it."""
    out, stack, t = [], [], None

    def close():
        end, name = stack.pop()
        if end > t:
            out.append((t, end, name))
        return max(t, end)

    for s, e, name, *_ in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            t = close()
        if stack:
            if s > t:
                out.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        t = close()
    return out


def _pair(step_spans, runs):
    """For each step span (sorted), the index of its program run (sorted)."""
    if len(step_spans) == len(runs):
        return list(range(len(runs)))
    starts = [s for s, _, _ in runs]
    out = []
    for s, _ in step_spans:
        k = bisect.bisect_left(starts, s)
        near = [j for j in (k - 1, k) if 0 <= j < len(starts)]
        out.append(min(near, key=lambda j: abs(starts[j] - s)))
    return out


def decode_split(modules, spans) -> dict:
    """The decode steps of the ``engine.decode`` spans: their count, the
    spans' seconds, and the device seconds of the paired decode runs
    (averaged over the chips), with both per step in ms; and the clock
    lag of each pairing. None where the trace has no decode step."""
    steps = sorted((s, e) for s, e, n, *_ in spans
                   if n == "engine.decode_step")
    decodes = merge((s, e) for s, e, n, *_ in spans if n == "engine.decode")
    if not steps or not decodes:
        return None
    in_decode = [any(a <= s and e <= b for a, b in decodes)
                 for s, e in steps]
    device, lags, chips = 0.0, [], 0
    for mods in modules.values():
        runs = sorted(m for m in mods if m[2] == DECODE_PROGRAM)
        if not runs:
            continue
        chips += 1
        for (s, _), j, inside in zip(steps, _pair(steps, runs), in_decode):
            lags.append(runs[j][0] - s)
            if inside:
                device += runs[j][1] - runs[j][0]
    n = sum(in_decode)
    span_s = length(decodes)
    out = {"steps": n, "span_s": span_s}
    if chips and n:
        device /= chips
        out.update(device_s=device,
                   device_ms_per_step=1000.0 * device / n,
                   overhead_ms_per_step=1000.0 * (span_s - device) / n,
                   clock_lag_ms={"min": 1000.0 * min(lags),
                                 "median": 1000.0 * statistics.median(lags)})
    return out


def reduce(modules, ops, spans) -> dict:
    """``modules``, ``ops``: {chip: [(start, end, name)]}; ``spans``: host
    spans [(start, end, name, ...)] of the engine and the harness."""
    if not spans:
        raise ValueError("the trace holds no host span of the engine or "
                         "the harness")
    lo = min(s[0] for s in spans)
    hi = max(s[1] for s in spans)
    serving = merge((s, e) for s, e, n, *_ in spans if n in SERVING)
    pieces = innermost([s for s in spans if s[2].startswith("engine.")])
    chips = sorted(set(modules) | set(ops))
    per_program = defaultdict(lambda: [0.0, 0])
    idle = defaultdict(float)
    for chip in chips:
        for s, e, name in modules.get(chip, ()):
            if s < hi and e > lo:
                per_program[name][0] += min(e, hi) - max(s, lo)
                per_program[name][1] += 1
        busy = merge((max(s, lo), min(e, hi)) for s, e, _ in
                     ops.get(chip) or modules.get(chip, ()))
        gaps = intersect(serving, _complement(busy, lo, hi))
        named = 0.0
        for s, e, name in _intersect_named(gaps, pieces):
            idle[name] += e - s
            named += e - s
        idle[NO_SPAN] += length(gaps) - named
    n = max(len(chips), 1)
    programs = sorted(per_program.items(), key=lambda kv: -kv[1][0])
    return {
        "chips_traced": len(chips),
        "window_s": hi - lo,
        "serving_s": length(serving),
        "programs": [[name, secs / n, runs // n]
                     for name, (secs, runs) in programs[:TOP]],
        "idle_in_serving": [[name, secs / n] for name, secs in
                            sorted(idle.items(), key=lambda kv: -kv[1])
                            if secs > 0],
        "decode": decode_split(modules, spans),
    }


def _complement(intervals, lo, hi):
    edges = [lo] + [t for iv in intervals for t in iv] + [hi]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
            if edges[k + 1] > edges[k]]


def _intersect_named(a, b):
    """Intersection of sorted disjoint intervals ``a`` with sorted disjoint
    named pieces ``b``: (start, end, name) of ``b``'s piece."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e, b[j][2]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def store_payload_ratio(events) -> float:
    """Mean over the recorder's ``store_bytes`` events of payload bytes
    over charged bytes (1.0: the store holds what it charges); None where
    there is none with a charge."""
    ratios = [ev["payload_bytes"] / ev["charged_bytes"] for ev in events
              if ev["kind"] == "store_bytes" and ev["charged_bytes"] > 0]
    return statistics.fmean(ratios) if ratios else None
