"""Host-clock spans that the harness records around calls into the program.

The program's own spans (``kv.*``) and counters (``KVPool.metrics``) are
read from the profiler trace (``trace.py``) and around the window
(``harness.py``).  Beside them the harness wraps the calls that the first
per-layer metrics time on the host clock: methods of the run's ``KVPool``
instance and functions of program modules, replaced by attribute for the
length of one run and put back by ``restore``.  A wrapper records only
while ``on`` is set (the measured window of a traced run); each recorded
span is also a ``jax.profiler.TraceAnnotation``, so that the device
trace can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Callable, Dict, List, Optional

_MISSING = object()


class Spans:
    def __init__(self):
        self.on = False
        self.seconds: Dict[str, float] = defaultdict(float)
        self.calls: Dict[str, int] = defaultdict(int)
        self.sizes: Dict[str, List[int]] = defaultdict(list)
        self.kept: Dict[str, list] = defaultdict(list)
        self._patched: list = []

    def _replace(self, obj, attr: str, fn: Callable):
        self._patched.append((obj, attr, vars(obj).get(attr, _MISSING)))
        setattr(obj, attr, fn)

    def time(self, obj, attr: str, name: str, *, size: bool = False,
             end: Optional[Callable[[], None]] = None):
        """Record a span around every call of ``obj.attr``.  ``size`` keeps
        ``len`` of the first argument; ``end`` runs inside the span after
        the call (to wait for the device)."""
        from jax.profiler import TraceAnnotation
        fn = getattr(obj, attr)

        def timed(*args, **kwargs):
            if not self.on:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            with TraceAnnotation(name):
                out = fn(*args, **kwargs)
                if end is not None:
                    end()
            self.seconds[name] += time.perf_counter() - t0
            self.calls[name] += 1
            if size:
                self.sizes[name].append(len(args[0]))
            return out

        self._replace(obj, attr, timed)

    def keep(self, obj, attr: str, name: str, pick: Callable):
        """Keep ``pick(args)`` of every call of ``obj.attr`` (a device array
        is kept as a reference: nothing waits for it inside the window)."""
        fn = getattr(obj, attr)

        def kept(*args, **kwargs):
            if self.on:
                self.kept[name].append(pick(args))
            return fn(*args, **kwargs)

        self._replace(obj, attr, kept)

    def watch(self, obj, attr: str, see: Callable[[object], None]):
        """Pass the result of every call of ``obj.attr`` to ``see``, whether
        or not ``on`` is set (set-up steers by these)."""
        fn = getattr(obj, attr)

        def watched(*args, **kwargs):
            out = fn(*args, **kwargs)
            see(out)
            return out

        self._replace(obj, attr, watched)

    def restore(self):
        for obj, attr, old in reversed(self._patched):
            if old is _MISSING:
                delattr(obj, attr)
            else:
                setattr(obj, attr, old)
        self._patched.clear()
