"""Device trace of a traced run, and its reduction to metrics.

``AnnotatingTracer`` is the program's own ``repro.obs.Tracer`` with each
span also opened as a ``jax.profiler.TraceAnnotation``, so the
program's host spans (decode, dispatch, device_wait, archive, propose,
front.fold, ...) land in the profiler's trace on the device's clock.
``load`` reads the trace's ``.xplane.pb`` with ``jax.profiler.
ProfileData``; ``reduce`` turns it into the busy time per device, the
device time per jitted executable, the busiest device operations and
the idle gaps labelled by the host span open during them.
"""

from __future__ import annotations

import glob
import heapq
import os
from typing import NamedTuple

import numpy as np

WINDOW_SPAN = "bench.window"


def annotating_tracer():
    import jax
    from repro.obs import Tracer

    class _Annotated:
        __slots__ = ("span", "ann")

        def __init__(self, span, ann):
            self.span, self.ann = span, ann

        def __enter__(self):
            self.ann.__enter__()
            self.span.__enter__()
            return self.span

        def __exit__(self, *exc):
            self.span.__exit__(*exc)
            return self.ann.__exit__(*exc)

    class AnnotatingTracer(Tracer):
        """A ``Tracer`` whose spans are also profiler annotations named
        ``<cat>.<name>``."""

        def __init__(self):
            super().__init__(record_events=False, rss_interval_s=0.0)

        def span(self, name, cat="sweep", track=None, **args):
            return _Annotated(super().span(name, cat, track, **args),
                              jax.profiler.TraceAnnotation(f"{cat}.{name}"))

    return AnnotatingTracer()


def profile_options():
    """Profiler options of a traced window: host annotations on, the
    Python function tracer off (it would trace every call the host
    makes and slow the host path being measured)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


class Event(NamedTuple):
    name: str
    start_ns: int
    end_ns: int


class Trace(NamedTuple):
    """What the reduction reads: per device, its operations and its
    executables (modules); the host spans; the window's bounds."""
    ops: dict          # device id -> [Event]
    modules: dict      # device id -> [Event]
    host: list         # [Event] of the benchmark's and program's spans
    window: tuple      # (start_ns, end_ns)


def load(trace_dir: str, host_names) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``.  Device planes
    are ``/device:TPU:<n>``; their "XLA Ops" and "XLA Modules" lines hold
    the operations and executables.  Host events are kept when their
    name is one the benchmark annotated."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    host_names = set(host_names) | {WINDOW_SPAN}
    ops, modules, host = {}, {}, []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is None:
                    continue
                dest.setdefault(dev, []).extend(
                    Event(e.name, int(e.start_ns),
                          int(e.start_ns + e.duration_ns))
                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name not in host_names:
                        continue
                    ev = Event(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns))
                    if e.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.end_ns)
                    else:
                        host.append(ev)
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return Trace(ops=ops, modules=modules, host=host, window=window)


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _clip(events, lo, hi) -> np.ndarray:
    iv = np.asarray([(max(e.start_ns, lo), min(e.end_ns, hi))
                     for e in events], np.int64).reshape(-1, 2)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce(trace: Trace, executables, top: int = 10) -> dict:
    """The traced window's numbers: ``window_s``; ``busy_s`` per device
    (union of its operations, or of its executables where no operation
    line exists); device seconds per named executable (an executable
    counts for every name in ``executables`` that its module name
    contains), summed over devices; the ``top`` operations by device
    seconds (named ``<executable>/<op>``); and the ``top`` labels of
    device-0 idle time, each gap charged to the host span that overlaps
    it most, innermost first."""
    lo, hi = trace.window
    window_s = (hi - lo) / 1e9
    devices = sorted(set(trace.ops) | set(trace.modules))
    busy, idle_by_label, op_time = {}, {}, {}
    exec_s = {name: 0.0 for name in executables}
    for dev in devices:
        mods = trace.modules.get(dev, [])
        ops = trace.ops.get(dev) or mods
        merged = _union(_clip(ops, lo, hi))
        busy[dev] = float((merged[:, 1] - merged[:, 0]).sum()) / 1e9
        for m in mods:
            d = (min(m.end_ns, hi) - max(m.start_ns, lo)) / 1e9
            if d > 0:
                for name in executables:
                    if name in m.name:
                        exec_s[name] += d
        mstart = np.asarray([m.start_ns for m in mods], np.int64)
        order = np.argsort(mstart, kind="stable")
        mstart = mstart[order]
        for e in trace.ops.get(dev, []):
            d = (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
            if d <= 0:
                continue
            k = int(np.searchsorted(mstart, e.start_ns, side="right")) - 1
            owner = mods[order[k]] if k >= 0 and \
                mods[order[k]].end_ns >= e.end_ns else None
            key = f"{owner.name if owner else '?'}/{e.name}"
            op_time[key] = op_time.get(key, 0.0) + d
        if dev == devices[0]:
            edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
            idle_by_label = _label_gaps(trace.host, edges[edges[:, 1] >
                                                         edges[:, 0]])
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return dict(window_s=window_s, busy_s=busy, exec_s=exec_s,
                device_ops=[[k, v] for k, v in rank(op_time)],
                idle_gaps=[[k, v] for k, v in rank(idle_by_label)])


def _label_gaps(host: list, gaps: np.ndarray) -> dict:
    """Idle seconds per label: each gap goes to the host span covering
    most of it, among equal cover the shortest (innermost) one, among
    those the first in ``host``; a gap no span covers is "no host span".

    One sweep over time, O((gaps + spans) log spans): the gaps are taken
    in order of start, the spans in order of start, and a heap keyed by
    end holds the spans begun before the gap ends.  A span that ends by
    a gap's start can cover no later gap and leaves the heap, so each gap
    looks only at the spans open across it or lying inside it.  Seconds
    are summed in the caller's order of gaps."""
    starts = [e.start_ns for e in host]
    ends = [e.end_ns for e in host]
    by_start = sorted(range(len(host)), key=starts.__getitem__)
    labels = ["no host span"] * len(gaps)
    heap: list[tuple[int, int]] = []      # (end_ns, index in host)
    nxt = 0
    pairs = gaps.tolist()
    for i in np.argsort(gaps[:, 0], kind="stable").tolist():
        g0, g1 = pairs[i]
        while nxt < len(by_start) and starts[by_start[nxt]] < g1:
            k = by_start[nxt]
            heapq.heappush(heap, (ends[k], k))
            nxt += 1
        while heap and heap[0][0] <= g0:
            heapq.heappop(heap)
        best = None
        for end, k in heap:
            cover = min(end, g1) - max(starts[k], g0)
            if cover > 0:
                key = (-cover, end - starts[k], k)
                if best is None or key < best:
                    best = key
        if best is not None:
            labels[i] = host[best[2]].name
    out: dict[str, float] = {}
    secs = ((gaps[:, 1] - gaps[:, 0]) / 1e9).tolist()
    for label, s in zip(labels, secs):
        out[label] = out.get(label, 0.0) + s
    return out
