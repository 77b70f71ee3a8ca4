"""One run of one benchmark cell: set-up, the measured window, the check.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
this module finds their files by name: ``configs/<config>.json``,
``mixes/<traffic>.json`` and the generator the mix names,
``generators/<generator>.py``.  Each per-layer metric is read by
``layer_metrics/<name>.py`` and each kernel's cost by ``costs/<kernel>.py``.
A later cell, mix, metric or kernel is a new file and a new entry.

A run drives the store's normal entry point, ``KVStore.submit_batch`` over
``DeviceBackend``, on one device:

1. set-up: build the pool, load every record with INSERTs (the YCSB load
   phase), then warm up every shape the window will use: UPDATE batches of
   each power-of-two size up to the tick's count, ticks of the mix, and GET
   batches of the read-back's size until the fingerprint-collision path
   has run.  Where the free pages left after the load are few enough for
   UPDATE traffic to use up, large UPDATE batches drain the slab until the
   owner's reclaim has run, and then until the reclaim is due again about
   ``RECLAIM_AT_TICK`` ticks into the window, so that every run's window
   holds one reclaim at the same point;
2. the window: ticks of the mix for ``seconds``; a tick submits its GETs
   as one batch, then its UPDATEs as one batch, and each call is timed
   from submit until every future's result is in hand and the pool's
   device arrays are ready.  The program's counters are read just before
   the window opens and just after it closes, never inside a timed call;
3. the check, after the window: ``reference.Replay`` replays every call
   made since the load began, a read-back of every record goes through
   ``KVStore`` and is checked too, and the index replicas are compared
   slot by slot.

A configuration's ``key_format`` says how a record number becomes the key
the store is given: ``int`` passes the number itself, ``ycsb`` builds
YCSB's own string key (``ycsb_key``).  The reference works on record
numbers, whatever the keys are.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from chipbench import reference
from chipbench.spans import Spans
from chipbench.trace import WINDOW_SPAN, Capture, Reduction

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
N_VALUES = 65536               # distinct values per run, drawn from the seed
RECLAIM_WARMUP_PAGES = 65536   # warm reclaim up when at most this many free
DRAIN_BATCH = 4096             # UPDATEs per set-up call that drains the slab
RECLAIM_AT_TICK = 32           # window tick at which the reclaim falls due
READBACK_BATCH = 4096          # GETs per call of the read-back
CHUNK_TICKS = 16               # ticks drawn at a time inside the window
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_file(path: Path):
    """Import a file of the benchmark as a module of its own."""
    name = "chipbench_" + "_".join(path.relative_to(HERE).with_suffix("")
                                   .parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def resolve(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell named ``name`` with its configuration and mix read in, and
    the metrics it reports."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; the workloads are "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        config=json.loads((REPO / cfg["file"]).read_text()),
        mix=json.loads((HERE / "mixes" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])])


def cell_of(config: str, traffic: str, bench: Optional[dict] = None) -> Cell:
    """A cell made of a configuration file and a mix file by their names,
    whether or not ``BENCHMARK.json`` lists the pair (``control.py`` runs
    such cells to show what keeps them out); it reports every metric."""
    bench = bench or benchmark()
    return Cell(
        name=f"{config}.{traffic}",
        config=json.loads((HERE / "configs" / f"{config}.json").read_text()),
        mix=json.loads((HERE / "mixes" / f"{traffic}.json").read_text()),
        chips=1, end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])


def ycsb_key(record: int) -> str:
    """YCSB's key of a record (``CoreWorkload.buildKeyName`` with
    ``orderedinserts`` false): ``user`` and the decimal of the 64-bit FNV-1
    hash of its 0-based number, made non-negative as Java's ``Math.abs``
    does."""
    h, v = 0xCBF29CE484222325, record - 1
    for _ in range(8):
        h = ((h ^ (v & 0xFF)) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
        v >>= 8
    if h >= 1 << 63:
        h -= 1 << 64
    return f"user{abs(h) if h != -(1 << 63) else h}"


def make_keys(cfg: dict) -> Optional[list]:
    """The key of each record number (index 0 unused), or None where the
    record number is the key."""
    fmt = cfg.get("key_format", "int")
    if fmt == "int":
        return None
    if fmt == "ycsb":
        return [None] + [ycsb_key(r) for r in range(1, cfg["records"] + 1)]
    raise ValueError(f"no key_format {fmt!r}; the formats are int, ycsb")


def make_values(value_bytes: int, seed: int) -> List[bytes]:
    """``N_VALUES`` distinct values of ``value_bytes`` bytes, from the seed.
    Where fewer distinct values exist, all of them, in seeded order."""
    rng = np.random.default_rng([seed, 0])
    if 256 ** value_bytes <= N_VALUES:
        n = 256 ** value_bytes
        return [int(i).to_bytes(value_bytes, "little")
                for i in rng.permutation(n)]
    buf = rng.bytes(N_VALUES * value_bytes)
    return [buf[i * value_bytes:(i + 1) * value_bytes]
            for i in range(N_VALUES)]


class Client:
    """Submits calls through ``KVStore`` and logs every call made since the
    load began: kind, record numbers, value indices, and each answer's
    status and value (held by reference)."""

    def __init__(self, store, values: List[bytes],
                 keys: Optional[list] = None):
        import jax
        from repro.core.api import Op
        self.store = store
        self.pool = store.backend.pool
        self.values = values
        self.keys = keys
        self.calls: list = []
        self.annotate = False           # a TraceAnnotation per call
        self._block = jax.block_until_ready
        self._make = {"get": Op.get, "insert": Op.insert,
                      "update": Op.update}

    def call(self, kind: str, keys: np.ndarray,
             vidx: Optional[np.ndarray] = None):
        """One ``submit_batch``; returns (start, results in hand, device
        ready) on the host clock."""
        make, values = self._make[kind], self.values
        names = keys.tolist()
        if self.keys is not None:
            names = [self.keys[k] for k in names]
        if vidx is None:
            ops = [make(k) for k in names]
        else:
            ops = [make(k, values[v]) for k, v in zip(names, vidx.tolist())]
        with _annotation(self.annotate, f"cb.{kind}"):
            t0 = time.perf_counter()
            results = [f.result() for f in self.store.submit_batch(ops)]
            t1 = time.perf_counter()
            p = self.pool
            self._block((p.index, p.log, p.chain, p.free_bitmap))
            t2 = time.perf_counter()
        self.calls.append((kind, keys, vidx, [r.status for r in results],
                           [r.value for r in results]))
        return t0, t1, t2


@dataclass
class Readings:
    """What the per-layer readers read (``layer_metrics/<name>.py``)."""
    cell: Cell
    ops: Dict[str, int]               # ops in the window, by kind
    calls: Dict[str, int]             # calls in the window, by kind
    frontend_s: float                 # host time from submit to results
    spans: Spans
    epoch_writers: List[int]          # active writers of each epoch
    compiles: int                     # program loads inside the window
    trace: Optional[Reduction]
    peaks: dict
    # the window's difference of every counter in ``KVPool.metrics``
    counters: Dict[str, int] = field(default_factory=dict)

    def cost(self, kernel: str):
        return load_file(HERE / "costs" / f"{kernel}.py")


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"peaks.json has no device kind {kind!r}")
    return table[kind]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, log: Callable[[str], None]) -> dict:
    """One run; returns the result line's object."""
    import jax
    from repro.core import codec
    from repro.core.api import KVStore
    from repro.serving import DeviceBackend, PoolConfig, kvpool

    dev = jax.devices()[0]
    peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else {}
    cfg, mix = cell.config, cell.mix
    records = int(cfg["records"])
    values = make_values(int(cfg["value_bytes"]), seed)
    gen = load_file(HERE / "generators" / f"{mix['generator']}.py").Ticks(
        mix, records, len(values), seed)

    compiles = [0]

    def on_event(name, _secs, **_kw):
        if name == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)
    backend = DeviceBackend(PoolConfig(**cfg["pool"]), seed=seed & 0x7FFFFFFF)
    store = KVStore(backend)
    pool = backend.pool
    client = Client(store, values, make_keys(cfg))
    spans = Spans()
    try:
        t = time.perf_counter()
        _load(client, cfg, records, len(values), seed)
        log(f"set-up: {t - t_start!r} s before the load, load "
            f"{time.perf_counter() - t!r} s")
        t = time.perf_counter()
        _warm_up(client, gen, spans, cfg, records, log)
        log(f"set-up: warm-up {time.perf_counter() - t!r} s")
        # what set-up made (the load's log above all) is never garbage:
        # keep the collector's passes in the window off it
        gc.collect()
        gc.freeze()
        if trace:
            _instrument(spans, pool, kvpool, codec, jax)
            capture = Capture()
            capture.start()
        lat: Dict[str, list] = {k: [] for k in gen.kinds}
        front_s = 0.0
        window = gen.stream("window")
        n_before = len(client.calls)
        c0 = compiles[0]
        counted = _counters(pool)
        spans.on = client.annotate = trace
        t_w0 = time.perf_counter()
        setup_s = t_w0 - t_start
        deadline = t_w0 + seconds
        with _annotation(trace, WINDOW_SPAN):
            done = False
            while not done:
                for tick in window.next(CHUNK_TICKS):
                    for kind in gen.kinds:
                        keys, vidx = tick[kind]
                        a, b, c = client.call(kind, keys, vidx)
                        lat[kind].append((c - a, len(keys)))
                        front_s += b - a
                    if time.perf_counter() >= deadline:
                        done = True
                        break
        window_s = time.perf_counter() - t_w0
        spans.on = client.annotate = False
        counters = {k: v - counted.get(k, 0)
                    for k, v in _counters(pool).items()}
        window_compiles = compiles[0] - c0
        stats = dev.memory_stats() or {}
        reduction = Reduction(capture.stop()) if trace else None
        epoch_writers = [int((np.asarray(s) >= 0).sum())
                         for s in spans.kept["snapshot_epoch"]]
    finally:
        gc.unfreeze()
        spans.restore()
        jax.monitoring.unregister_event_duration_listener(on_event)

    checks, failed = _check(client, records, n_before, log)
    index_host = np.asarray(pool.index)
    checks["unconverged_slots"] = (reference.unconverged_slots(index_host), 0)
    del index_host, client, store, backend, pool

    ops = {k: sum(n for _, n in lat[k]) for k in gen.kinds}
    attempted = sum(ops.values())
    e2e = {
        "ops_per_s": (attempted / window_s, "ops/s"),
        "get_p95_ms": (_p95(lat.get("get")), "ms"),
        "update_p95_ms": (_p95(lat.get("update")), "ms"),
        "setup_s": (setup_s, "s"),
    }
    metrics = {}
    breakdown = None
    if trace:
        readings = Readings(cell, ops, {k: len(v) for k, v in lat.items()},
                            front_s, spans, epoch_writers, window_compiles,
                            reduction, peaks, counters)
        for m in cell.per_layer:
            v = load_file(HERE / "layer_metrics" / f"{m['name']}.py").read(
                readings)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": reduction.top_modules(),
                     "idle_gaps": reduction.top_idle()}
    else:
        for m in cell.end_to_end:
            v, unit = e2e[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": unit}
    log(f"window: {window_s!r} s, {attempted} ops, "
        f"{sum(len(v) for v in lat.values())} calls, "
        f"{window_compiles} program loads inside; the program's counters "
        f"over it: {json.dumps(counters, sort_keys=True)}")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    if trace:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    out = {"correct": all(v <= lim for v, lim in checks.values()),
           "attempted": attempted, "failed": failed, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def _counters(pool) -> Dict[str, int]:
    """The program's counters (``KVPool.metrics``), read on the host."""
    return pool.metrics.snapshot()["counters"]


def _annotation(on: bool, name: str):
    import contextlib
    if not on:
        return contextlib.nullcontext()
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _p95(lat) -> Optional[float]:
    """95th percentile over ops, each op taking its call's latency, in ms."""
    if not lat:
        return None
    secs = np.repeat([s for s, _ in lat], [n for _, n in lat])
    return float(np.percentile(secs, 95)) * 1e3


def _load(client: Client, cfg: dict, records: int, n_values: int, seed: int):
    """The YCSB load phase: INSERT every record, in seeded order."""
    rng = np.random.default_rng([seed, 4])
    order = rng.permutation(records).astype(np.int64) + 1
    vidx = rng.integers(0, n_values, size=records)
    batch = int(cfg["load_batch"])
    for i in range(0, records, batch):
        client.call("insert", order[i:i + batch], vidx[i:i + batch])


def _warm_up(client: Client, gen, spans: Spans, cfg: dict, records: int,
             log: Callable[[str], None]):
    """Run every shape the window will use, once, before it opens."""
    pool = client.pool
    warm = gen.stream("warmup")
    n_upd = gen.counts.get("update", 0)

    def update(size):
        size = min(size, records)
        client.call("update", warm.distinct_keys(size),
                    warm.rng.integers(0, gen.n_values, size))

    size = 1
    while size < n_upd:                 # UPDATE batches of every size class
        update(size)
        size *= 2
    for tick in warm.next(8):
        for kind in gen.kinds:
            client.call(kind, *tick[kind])
    seen = _Seen()
    spans.watch(pool, "_search_all_candidates", seen.collision)
    spans.watch(pool, "reclaim", seen.reclaimed)
    spans.watch(pool, "alloc_pages", seen.allocated)
    try:
        # GETs of the read-back's size: that shape is warm for the check,
        # and a batch this large always holds a fingerprint collision
        for _ in range(8):
            if seen.collisions:
                break
            client.call("get", warm.distinct_keys(min(READBACK_BATCH,
                                                      records)))
        free = cfg["pool"]["n_pages"] - records
        if n_upd and free <= RECLAIM_WARMUP_PAGES:
            # a batch no larger than half the free pages, so that the
            # reclaim it meets returns enough pages for the rest of it
            drain = min(DRAIN_BATCH, max(free // 2, n_upd))
            for _ in range(free // drain + 4):
                if seen.reclaims:
                    break
                update(drain)
            target = RECLAIM_AT_TICK * n_upd
            for size in (drain, n_upd):
                while seen.left is not None and seen.left - size >= target:
                    update(size)
        log(f"warm-up: collision path {seen.collisions} calls, reclaims "
            f"{seen.reclaims}, {seen.left} pages left in the slab")
    finally:
        spans.restore()


class _Seen:
    """What set-up steers by, from the results of the pool's own calls:
    collision-path calls, reclaims, and the pages left in the owner's slab
    since the last reclaim (what it returned, less what was allocated)."""

    def __init__(self):
        self.collisions = 0
        self.reclaims = 0
        self.left: Optional[int] = None

    def collision(self, _out):
        self.collisions += 1

    def reclaimed(self, n):
        if n > 0:
            self.reclaims += 1
            self.left = int(n)

    def allocated(self, pages):
        if self.left is not None:
            self.left -= int((np.asarray(pages) >= 0).sum())


def _instrument(spans: Spans, pool, kvpool, codec, jax):
    """Spans of the traced window: the pool's methods, the codec, and the
    writers of every SNAPSHOT epoch."""

    def ready():
        jax.block_until_ready((pool.index, pool.log, pool.chain,
                               pool.free_bitmap))

    spans.time(pool, "search", "cb.pool.search", size=True)
    for name in ("alloc_pages", "reclaim", "write_pages"):
        spans.time(pool, name, f"cb.pool.{name}")
    spans.time(pool, "insert_batch", "cb.pool.insert_batch", end=ready)
    spans.time(codec, "encode_value", "cb.codec.encode_value")
    spans.time(codec, "decode_value", "cb.codec.decode_value")
    spans.keep(kvpool, "snapshot_epoch", "snapshot_epoch",
               pick=lambda args: args[1])


def _check(client: Client, records: int, first_window: int,
           log: Callable[[str], None]):
    """Replay every call, then read back every record through the store.
    Returns the checks as name -> (number, limit), and the window's failed
    ops (wrong or FULL)."""
    replay = reference.Replay(client.values, records)
    bad = {"load": 0, "wrong": 0, "full": 0}
    window_failed = 0
    for i, call in enumerate(client.calls):
        w0, f0 = replay.wrong, replay.full
        replay.apply(*call)
        dw, df = replay.wrong - w0, replay.full - f0
        if call[0] == "insert":
            bad["load"] += dw + df
        else:
            bad["wrong"] += dw
            bad["full"] += df
        if i >= first_window:
            window_failed += dw + df

    every = np.arange(1, records + 1, dtype=np.int64)
    n_logged = len(client.calls)
    for i in range(0, records, READBACK_BATCH):
        client.call("get", every[i:i + READBACK_BATCH])
    w0 = replay.wrong
    for call in client.calls[n_logged:]:
        replay.apply(*call)
    for e in replay.examples:
        log(f"reference: {e}")
    checks = {"load_not_ok": (bad["load"], 0),
              "wrong_answers": (bad["wrong"], 0),
              "full_answers": (bad["full"], 0),
              "readback_wrong": (replay.wrong - w0, 0)}
    return checks, window_failed
