"""The profiler trace of a measured window, and its reduction to numbers.

``capture_window`` runs ``jax.profiler`` around the window into a
temporary directory (under ``TMPDIR``), reads the ``.xplane.pb`` it wrote
into plain lists and deletes the directory.  ``Reduction`` works on those
lists only, so the tests can feed it a small synthetic trace:

    {"devices": [{"name": "/device:TPU:0",
                  "ops": [[name, start_ns, dur_ns], ...],       # XLA Ops
                  "modules": [[name, start_ns, dur_ns], ...]}], # XLA Modules
     "host": [[name, start_ns, dur_ns], ...]}           # cb.* and kv.* spans

Host spans are the harness's own (``cb.*``) and every span the program
opens (``kv.*``), all on one host thread, so they nest.  The window is the
host span ``cb.window``.  Busy time is the union of the intervals in which
an op ran on a device, inside the window, averaged over the devices; idle
is the rest of the window.  Idle time is put down to what the host was
doing meanwhile: the innermost span of either family open at each moment,
or ``harness`` where none is (the harness's own work between calls).  For
every span name the reduction also gives its total seconds inside the
window, its self seconds (the total less the spans directly inside it, of
either family) and its number of calls.
"""
from __future__ import annotations

import glob
import re
import shutil
import tempfile
from typing import Dict, List, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIXES = ("cb.", "kv.")
WINDOW_SPAN = "cb.window"
_RUN_ID = re.compile(r"\(\d+\)$")


class Capture:
    """Start and stop the profiler; ``stop`` returns the trace as lists."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1          # TraceAnnotations, no more
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> dict:
        import jax
        jax.profiler.stop_trace()
        try:
            paths = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)
            if len(paths) != 1:
                raise RuntimeError(f"expected one .xplane.pb, found {paths}")
            return read_xspace(paths[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def read_xspace(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            missing = {OPS_LINE, MODULES_LINE} - set(lines)
            if missing:
                raise RuntimeError(f"{plane.name} has no line "
                                   f"{sorted(missing)}; its lines are "
                                   f"{sorted(lines)}")
            devices.append({"name": plane.name, **{
                key: [[e.name, e.start_ns, e.duration_ns]
                      for e in lines[line].events]
                for key, line in (("ops", OPS_LINE),
                                  ("modules", MODULES_LINE))}})
        elif plane.name == HOST_PLANE:
            host += [[e.name, e.start_ns, e.duration_ns]
                     for ln in plane.lines for e in ln.events
                     if e.name.startswith(SPAN_PREFIXES)]
    return {"devices": devices, "host": host}


def _arrays(events) -> Tuple[List[str], np.ndarray, np.ndarray]:
    names = [e[0] for e in events]
    start = np.array([e[1] for e in events], np.float64)
    end = start + np.array([e[2] for e in events], np.float64)
    return names, start, end


def merge(start: np.ndarray, end: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Union of intervals, as sorted disjoint intervals."""
    if not len(start):
        return start, end
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    heads = np.flatnonzero(first)
    tails = np.r_[heads[1:] - 1, len(s) - 1]
    return s[heads], reach[tails]


def _clip(start, end, t0, t1):
    s, e = np.maximum(start, t0), np.minimum(end, t1)
    keep = e > s
    return s[keep], e[keep], keep


class Reduction:
    def __init__(self, trace: dict):
        wins = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
        if len(wins) != 1:
            raise ValueError(f"the trace holds {len(wins)} {WINDOW_SPAN} "
                             f"spans, not one")
        _, self.t0, dur = wins[0]
        self.t1 = self.t0 + dur
        self.window_s = dur / 1e9
        if not trace["devices"]:
            raise ValueError("the trace holds no TPU device plane")
        n_dev = len(trace["devices"])
        # the host's timeline: innermost open span of each elementary segment
        spans = sorted((e for e in trace["host"] if e[0] != WINDOW_SPAN),
                       key=lambda x: (x[1], -x[2]))
        names, s, e = _arrays(spans)
        depth, parent = _nesting(s, e)
        self._span_times(names, s, e, parent)
        cuts = np.unique(np.clip(np.r_[self.t0, self.t1, s, e],
                                 self.t0, self.t1))
        seg_a, seg_b = cuts[:-1], cuts[1:]
        seg_label = _innermost((seg_a + seg_b) / 2, s, e, depth)
        labels = names + ["harness"]            # index -1 is "harness"
        busy = 0.0
        idle = np.zeros(len(labels))
        self.modules: Dict[str, float] = {}
        for dev in trace["devices"]:
            _, os_, oe = _arrays(dev["ops"])
            os_, oe, _ = _clip(os_, oe, self.t0, self.t1)
            ms, me = merge(os_, oe)
            busy += float((me - ms).sum())
            seg_idle = (seg_b - seg_a) - (_busy_upto(ms, me, seg_b)
                                          - _busy_upto(ms, me, seg_a))
            np.add.at(idle, seg_label, seg_idle)
            mnames, ms_, me_ = _arrays(dev["modules"])
            ms_, me_, keep = _clip(ms_, me_, self.t0, self.t1)
            for name, d in zip((n for n, k in zip(mnames, keep) if k),
                               me_ - ms_):
                name = _RUN_ID.sub("", name)
                self.modules[name] = self.modules.get(name, 0.0) + d / 1e9
        self.busy_s = busy / n_dev / 1e9
        self.idle: Dict[str, float] = {}
        for label, t in zip(labels, idle):
            if t > 0:
                self.idle[label] = self.idle.get(label, 0.0) + t / n_dev / 1e9

    def _span_times(self, names, s, e, parent):
        """Total and self seconds inside the window, and calls, by name."""
        dur = np.clip(np.minimum(e, self.t1) - np.maximum(s, self.t0),
                      0.0, None)
        child = parent >= 0
        inner = np.bincount(parent[child], weights=dur[child],
                            minlength=len(s))
        inside = (e >= self.t0) & (s <= self.t1)
        self.span_total_s: Dict[str, float] = {}
        self.span_self_s: Dict[str, float] = {}
        self.span_calls: Dict[str, int] = {}
        for i in np.flatnonzero(inside):
            n = names[i]
            self.span_total_s[n] = self.span_total_s.get(n, 0.0) + dur[i] / 1e9
            self.span_self_s[n] = (self.span_self_s.get(n, 0.0)
                                   + (dur[i] - inner[i]) / 1e9)
            self.span_calls[n] = self.span_calls.get(n, 0) + 1

    def module_seconds(self, prefix: str) -> float:
        return sum(t for n, t in self.modules.items() if n.startswith(prefix))

    def top_modules(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.modules.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.idle.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _busy_upto(ms: np.ndarray, me: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Busy time before each ``t``, given sorted disjoint busy intervals."""
    if not len(ms):
        return np.zeros(len(t))
    cum = np.r_[0.0, np.cumsum(me - ms)]
    k = np.searchsorted(ms, t, side="right")
    j = np.maximum(k - 1, 0)
    part = np.clip(np.minimum(t, me[j]) - ms[j], 0.0, None)
    return np.where(k > 0, cum[j] + part, 0.0)


def _nesting(s: np.ndarray, e: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Depth of each span, and the index of the span directly around it (-1
    where none is).  Spans are sorted by start, longest first, and nest
    (one thread)."""
    depth = np.zeros(len(s), int)
    parent = np.full(len(s), -1)
    stack: List[int] = []
    for i in range(len(s)):
        while stack and e[stack[-1]] <= s[i]:
            stack.pop()
        depth[i] = len(stack)
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    return depth, parent


def _innermost(t: np.ndarray, s: np.ndarray, e: np.ndarray,
               depth: np.ndarray) -> np.ndarray:
    """Index of the innermost span open at each ``t``, or -1 where none is
    (spans as ``_nesting`` takes them, with its depths)."""
    out = np.full(len(t), -1)
    for d in range(int(depth.max(initial=-1)), -1, -1):
        at = np.flatnonzero(depth == d)
        j = np.searchsorted(s[at], t, side="right") - 1
        hit = (out < 0) & (j >= 0)
        hit[hit] = e[at[j[hit]]] > t[hit]
        out[hit] = at[j[hit]]
    return out
