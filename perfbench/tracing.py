"""Host spans around the calls into each layer, and the reduction of a
profiler trace to device busy time, idle gaps and top device operations.

Spans are recorded on the host clock (``perf_counter_ns``) and, inside
the same ``with``, as ``jax.profiler.TraceAnnotation`` so a traced run
carries them on the device trace's clock.
"""
from __future__ import annotations

import glob
import os
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field

import jax

# the device plane line whose events are the programs that ran: one event
# per execution of a jitted program (the "XLA Ops" line nests each loop's
# body inside the loop and holds millions of events)
DEVICE_OPS_LINE = "XLA Modules"


class Spans:
    """Completed spans as (name, start_ns, end_ns), on the host clock."""

    def __init__(self):
        self.items: list[tuple[str, int, int]] = []

    @contextmanager
    def span(self, name: str):
        """Record the body as span ``name`` (and a trace annotation)."""
        t0 = time.perf_counter_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self.items.append((name, t0, time.perf_counter_ns()))

    def seconds(self, name: str, t0_ns: int = 0, t1_ns: int = 2**63) -> float:
        """Summed seconds of spans ``name`` that started in [t0, t1)."""
        return sum(e - s for n, s, e in self.items
                   if n == name and t0_ns <= s < t1_ns) / 1e9


def union_seconds(intervals) -> float:
    """Length in seconds of the union of (start_ns, end_ns) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    """Stretches of [t0, t1] that no interval covers."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def _open_span(host_spans, t: float) -> str:
    """Name of the innermost (shortest) host span open at time ``t``."""
    best = None
    for name, s, e in host_spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside any span"


@dataclass
class TraceSummary:
    """What a traced window says about the device."""

    window_s: float
    busy_s: float            # union of op intervals, averaged over chips
    chips: int
    device_ops: list = field(default_factory=list)
    idle_gaps: list = field(default_factory=list)


def summarize(device_ops: list, host_spans: list, t0: int, t1: int,
              top: int = 10) -> TraceSummary:
    """Reduce ``device_ops`` (one list of (name, start_ns, end_ns) per
    chip) and ``host_spans`` ((name, start_ns, end_ns)) over the window
    [t0, t1] on the trace's clock."""
    chips = len(device_ops)
    busy, by_name = 0.0, defaultdict(int)
    idle = []
    for ops in device_ops:
        iv = [(max(s, t0), min(e, t1)) for _, s, e in ops
              if e > t0 and s < t1]
        busy += union_seconds(iv)
        for name, s, e in ops:
            if e > t0 and s < t1:
                by_name[name] += min(e, t1) - max(s, t0)
        idle += gaps(iv, t0, t1)
    idle.sort(key=lambda g: g[0] - g[1])
    return TraceSummary(
        window_s=(t1 - t0) / 1e9, busy_s=busy / max(chips, 1),
        chips=chips,
        device_ops=[[n, d / 1e9] for n, d in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[_open_span(host_spans, (s + e) / 2), (e - s) / 1e9]
                   for s, e in idle[:top]])


def read_trace(logdir: str, window_span: str, prefix: str = "bench."
               ) -> TraceSummary:
    """Summarize the newest ``.xplane.pb`` under ``logdir``: the window is
    the host span ``window_span``, device operations are the program
    executions on each TPU plane's ``XLA Modules`` line (named without
    their fingerprint), host spans those named ``prefix*``."""
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {logdir}")
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    device_ops, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [(ev.name.split("(", 1)[0], ev.start_ns,
                    ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == DEVICE_OPS_LINE
                   for ev in line.events]
            if ops:
                device_ops.append(ops)
        elif plane.name.startswith("/host:"):
            host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(prefix)]
    win = [(s, e) for n, s, e in host if n == window_span]
    if not win:
        raise ValueError(f"the trace has no {window_span!r} span")
    t0, t1 = win[0]
    return summarize(device_ops, [h for h in host if h[0] != window_span],
                     t0, t1)
