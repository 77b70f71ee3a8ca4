"""Benchmark driver: one function per paper table/figure + roofline +
serving.  Prints ``name,us_per_call,derived`` CSV rows per bench and writes
the full row dump to bench_results.json.

    PYTHONPATH=src python -m benchmarks.run [--only fig13] [--skip-serving]

Regression gate: ``--compare benchmarks/BASELINE.json`` checks this run's
claim metrics (``claim_metrics``) against a committed baseline and exits
non-zero on any >10% regression in the metric's bad direction
(percentage-point metrics additionally need a >1.5-point absolute move, so
wall-clock ratio noise does not flap the gate).  ``--claims-out PATH``
writes the current metrics in the baseline format; refresh the committed
baseline with it when a PR intentionally shifts performance.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--skip-serving", action="store_true")
    ap.add_argument("--out", default="bench_results.json")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a registry metrics snapshot (JSON) of a "
                         "seeded churn-storm telemetry run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto/Chrome-trace JSON of the same "
                         "telemetry run (load at ui.perfetto.dev)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="regression gate: compare this run's claim "
                         "metrics against a baseline; exit 1 on any >10% "
                         "regression")
    ap.add_argument("--claims-out", default=None, metavar="PATH",
                    help="write this run's claim metrics as a baseline "
                         "JSON (commit as benchmarks/BASELINE.json)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import figures, roofline
    benches = [(f.__name__, f) for f in figures.ALL_FIGURES]
    benches.append(("trace_overhead", trace_overhead))
    benches.append(("obs_overhead", obs_overhead))
    benches.append(("explore_dpor", explore_dpor))
    benches.append(("roofline", roofline.run))
    if not args.skip_serving:
        from . import serving_bench
        benches.append(("serving", serving_bench.run))

    if args.only:
        selected = [(n, f) for n, f in benches if args.only in n]
        if not selected:
            # exit non-zero with the menu instead of silently running
            # nothing and writing an empty results file
            ap.error(f"--only {args.only!r} matches no benchmark; "
                     f"available: {', '.join(n for n, _ in benches)}")
        benches = selected

    all_rows = []
    print("name,us_per_call,derived")
    for name, fn in benches:
        t0 = time.perf_counter()
        try:
            rows = fn()
            dt = (time.perf_counter() - t0) * 1e6
            derived = summarize(name, rows)
            print(f"{name},{dt:.0f},{derived}", flush=True)
            all_rows.extend(rows)
        except Exception as e:  # pragma: no cover
            print(f"{name},FAIL,{e!r}")
            raise
    with open(args.out, "w") as f:
        json.dump(all_rows, f, indent=1, default=float)
    if args.metrics_out or args.trace_out:
        export_telemetry(args.metrics_out, args.trace_out)
    if args.claims_out:
        with open(args.claims_out, "w") as f:
            json.dump(claim_metrics(all_rows), f, indent=1, sort_keys=True)
        print(f"claim metrics -> {args.claims_out}")
    regressed = False
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        regressed = not compare_baseline(claim_metrics(all_rows), baseline)
    validate_claims(all_rows)
    if regressed:
        sys.exit(1)


def trace_overhead():
    """Verb-tracer overhead on the fleet tick path (sanitizer suite guard).

    Three modes over the identical seeded YCSB-A fleet workload:
    ``off`` (no tracer attached — the bare ``if tracer is None`` hook),
    ``paused`` (tracer attached, recording disabled — the "leave it on in
    production" mode), and ``recording``.  Each mode reports the median
    us/tick over repeats; the claims check asserts the disabled-mode
    (paused) overhead stays under 3% of the detached baseline.
    """
    import gc
    import statistics

    from repro.analysis.trace import VerbTracer
    from repro.core import FuseeCluster
    from .common import YCSB, fleet_dmconfig

    n_clients, n_keys, repeats, batches = 64, 256, 5, 3
    mix, value_words = YCSB["A"], 8

    def one_run(mode):
        """Build one cluster and time `batches` successive op waves on it,
        returning the per-tick cost of each wave."""
        cfg = fleet_dmconfig(n_clients, n_keys)
        cl = FuseeCluster(cfg, num_clients=n_clients, seed=21)
        sched, fleet = cl.scheduler, cl.fleet()
        tr = None
        if mode != "off":
            tr = VerbTracer(capacity=1 << 16).attach(cl.pool)
            if mode == "paused":
                tr.pause()
        for k in range(n_keys):
            sched.submit(k % n_clients, "insert", k, [k] * value_words)
        fleet.run()
        wl = cl.rng.stream("workload")
        kinds = list(mix)
        weights = [mix[k] for k in kinds]
        samples = []
        for _ in range(batches):
            for i in range(n_clients * 8):
                kind = kinds[int(wl.choice(len(kinds), p=weights))]
                key = int(wl.integers(n_keys))
                v = [i] * value_words if kind in ("insert", "update") \
                    else None
                sched.submit(i % n_clients, kind, key, v)
            gc.collect()
            gc.disable()                 # GC pauses are the loudest noise
            try:
                t0 = time.perf_counter()
                ticks0 = sched.tick
                fleet.run()
                dt = time.perf_counter() - t0
            finally:
                gc.enable()
            samples.append(dt * 1e6 / max(1, sched.tick - ticks0))
        return samples

    modes = ("off", "paused", "recording")
    one_run("off")                       # warmup: JIT / allocator caches
    times = {m: [] for m in modes}
    for _ in range(repeats):             # interleaved: drift hits all modes
        for m in modes:
            times[m].extend(one_run(m))
    # min-of-repeats: scheduling noise is one-sided additive, so the
    # fastest observation is the cleanest estimate of the true cost
    best = {m: min(times[m]) for m in modes}
    return [{"bench": "trace_overhead", "mode": m,
             "us_per_tick": best[m],
             "us_per_tick_median": statistics.median(times[m]),
             "overhead_pct": 100.0 * (best[m] / best["off"] - 1.0)}
            for m in modes]


def obs_overhead():
    """Observability-hub overhead on the fused fleet tick path.

    Three modes over the identical seeded YCSB-A fleet workload:
    ``detached`` (``cluster.detach_obs()`` — every hook site collapses to
    one attribute load + ``is None`` test), ``attached`` (the default
    always-on hub: flight recorder, latency histograms, heat sketch, and
    the per-MN load series all recording), and ``profiled`` (attached
    hub + the hot-key/skew monitor enabled — the full online profiling
    surface; the verb tracer's separate cost is ``trace_overhead``'s
    business).  Each mode reports us/tick; the claims check asserts both
    attached recording AND the profiled mode cost < 5% over the detached
    baseline, which is what justifies leaving them on for the life of a
    cluster.
    """
    import gc
    import statistics

    from repro.core import FuseeCluster
    from .common import YCSB, fleet_dmconfig

    n_clients, n_keys, repeats, batches = 64, 256, 5, 3
    mix, value_words = YCSB["A"], 8

    def one_run(mode):
        cfg = fleet_dmconfig(n_clients, n_keys)
        cl = FuseeCluster(cfg, num_clients=n_clients, seed=23)
        if mode == "detached":
            cl.detach_obs()
        elif mode == "profiled":
            cl.enable_hotspot()
        sched, fleet = cl.scheduler, cl.fleet()
        for k in range(n_keys):
            sched.submit(k % n_clients, "insert", k, [k] * value_words)
        fleet.run()
        wl = cl.rng.stream("workload")
        kinds = list(mix)
        weights = [mix[k] for k in kinds]
        samples = []
        for _ in range(batches):
            for i in range(n_clients * 8):
                kind = kinds[int(wl.choice(len(kinds), p=weights))]
                key = int(wl.integers(n_keys))
                v = [i] * value_words if kind in ("insert", "update") \
                    else None
                sched.submit(i % n_clients, kind, key, v)
            gc.collect()
            gc.disable()
            try:
                t0 = time.perf_counter()
                ticks0 = sched.tick
                fleet.run()
                dt = time.perf_counter() - t0
            finally:
                gc.enable()
            samples.append(dt * 1e6 / max(1, sched.tick - ticks0))
        return samples

    modes = ("detached", "attached", "profiled")
    one_run("detached")                  # warmup: JIT / allocator caches
    times = {m: [] for m in modes}
    for _ in range(repeats):             # interleaved: drift hits all modes
        for m in modes:
            times[m].extend(one_run(m))
    best = {m: min(times[m]) for m in modes}
    return [{"bench": "obs_overhead", "mode": m,
             "us_per_tick": best[m],
             "us_per_tick_median": statistics.median(times[m]),
             "overhead_pct": 100.0 * (best[m] / best["detached"] - 1.0)}
            for m in modes]


def export_telemetry(metrics_path=None, trace_path=None, *, seed=33):
    """Seeded churn-storm telemetry run for the CI artifacts: drives a
    crash/recover/add-MN storm on the fleet engine, then writes the
    registry snapshot (``--metrics-out``) and a Perfetto trace of the
    fault-triggered flight dump (``--trace-out``).  Deterministic: the
    metrics JSON is byte-identical for a given seed."""
    import os
    import tempfile

    from repro.core import (ClientCrashed, DMConfig, FaultPlan,
                            FuseeCluster, Op)
    from repro.obs import flight_to_perfetto, load_flight, metrics_to_json

    n_clients, n_mns, repl, total_ops = 6, 5, 3, 160
    with tempfile.TemporaryDirectory() as td:
        cl = FuseeCluster(DMConfig(num_mns=n_mns, replication=repl,
                                   region_words=1 << 15, regions_per_mn=16,
                                   index_shards=4),
                          num_clients=n_clients, seed=seed,
                          obs_dump_dir=td)
        plan = FaultPlan.storm(cl.rng.stream("faults"),
                               clients=range(n_clients), mns=n_mns,
                               replication=repl, n_client_crashes=2,
                               n_mn_crashes=1, n_add_mns=1,
                               remove_added=True, first_op=10, spacing=14,
                               recover_delay=8)
        cl.inject(plan)
        fleet = cl.fleet()
        stores = {c: cl.store(c, max_inflight=0) for c in range(n_clients)}
        submitted = 0
        while submitted < total_ops:
            for c in range(n_clients):
                if submitted >= total_ops:
                    break
                k = submitted
                submitted += 1
                try:
                    stores[c].submit(Op.put(k, [k, c]))
                except ClientCrashed:
                    pass
            for _ in range(4):
                if cl.scheduler.has_work():
                    fleet.tick()
        fleet.run()
        if cl.migrator.busy:
            cl.migrator.drive()
            fleet.run()
        if metrics_path:
            metrics_to_json(cl.metrics(), metrics_path)
            print(f"telemetry: metrics snapshot -> {metrics_path}")
        if trace_path:
            # prefer the first fault-triggered dump; fall back to a
            # manual end-of-run dump if the storm somehow never fired
            dumps = sorted(cl.obs.dumped.values())
            path = dumps[0] if dumps else cl.obs.dump("manual", force=True)
            flight_to_perfetto(load_flight(path), trace_path)
            print(f"telemetry: perfetto trace ({os.path.basename(path)}) "
                  f"-> {trace_path}")


def explore_dpor():
    """Model-checker bench on the 2-client/1-key insert-race scope.

    Three measurements:

    * ``dpor`` — the real checker (DPOR + sleep sets), run twice; the
      repeat must reproduce the state count AND the order-sensitive
      visit digest bit-identically (the determinism claim).
    * ``dedup`` — exploration with DPOR off (every enabled choice from
      every state), kept tractable by state-hash dedup cuts.  This run
      doubles as ground truth for the reachable-state count.
    * ``naive`` — true naive enumeration (no reduction, no dedup): every
      maximal schedule, every tree node.  Running it is infeasible, so
      it is counted EXACTLY instead: a replay-driven BFS builds the full
      state graph (possible because ``dedup`` proved it small), and a
      DP over that DAG counts the enumeration tree's transitions and
      maximal schedules a no-reduction DFS would execute.

    The claims check asserts dpor-explored transitions prune >= 5x vs
    the naive enumeration tree, and determinism across repeats.
    """
    from repro.analysis.explore import SCOPES, Explorer, state_hash

    scope = "insert_race"
    t0 = time.perf_counter()
    r1 = Explorer(scope).run()
    dpor_s = time.perf_counter() - t0
    r2 = Explorer(scope).run()
    deterministic = (r1.states == r2.states
                     and r1.executions == r2.executions
                     and r1.visit_digest == r2.visit_digest)
    t0 = time.perf_counter()
    rd = Explorer(scope, naive=True).run()
    dedup_s = time.perf_counter() - t0

    # --- exact naive-enumeration count: BFS the state graph by replay,
    # then DP.  succ[h] holds one entry PER CHOICE (two choices reaching
    # the same state are distinct tree edges).
    build = SCOPES[scope].build
    succ = {}
    root = build()
    h0 = state_hash(root.cluster)
    frontier = [(h0, ())]
    succ[h0] = None
    edges = 0
    while frontier:
        h, sched = frontier.pop()
        setup = build()
        cl = setup.cluster
        for ch in sched:
            cl.fire(ch)
        cs = cl.choices()
        outs = []
        for i, ch in enumerate(cs):
            if i > 0:                      # rebuild: fire() mutates
                setup = build()
                cl = setup.cluster
                for c in sched:
                    cl.fire(c)
            cl.fire(ch)
            h2 = state_hash(cl)
            outs.append(h2)
            edges += 1
            if h2 not in succ:
                succ[h2] = None
                frontier.append((h2, sched + (ch,)))
        succ[h] = outs

    import sys as _sys
    _sys.setrecursionlimit(100_000)
    tree_memo, sched_memo = {}, {}

    def tree_transitions(h):               # nodes the unreduced DFS fires
        if h not in tree_memo:
            tree_memo[h] = sum(1 + tree_transitions(t) for t in succ[h])
        return tree_memo[h]

    def schedules(h):                      # maximal schedules it executes
        if h not in sched_memo:
            sched_memo[h] = sum(schedules(t) for t in succ[h]) \
                if succ[h] else 1
        return sched_memo[h]

    naive_transitions = tree_transitions(h0)
    naive_schedules = schedules(h0)
    dpor_work = r1.transitions + r1.replay_fires
    return [{
        "bench": "explore", "scope": scope,
        "dpor_states": r1.states, "dpor_executions": r1.executions,
        "dpor_transitions": r1.transitions,
        "dpor_replay_fires": r1.replay_fires,
        "dpor_work": dpor_work, "dpor_s": dpor_s,
        "dpor_states_per_s": r1.states / max(dpor_s, 1e-9),
        "deterministic": deterministic, "visit_digest": r1.visit_digest,
        "dedup_states": rd.states, "dedup_executions": rd.executions,
        "dedup_s": dedup_s,
        "graph_states": len(succ), "graph_edges": edges,
        "naive_transitions": float(naive_transitions),
        "naive_schedules": float(naive_schedules),
        "reduction_transitions": naive_transitions / max(dpor_work, 1),
        "reduction_schedules": naive_schedules / max(r1.executions, 1),
    }]


# ------------------------------------------------------- regression gate
# metric fields worth gating, by good direction.  Simulated metrics
# (mops, RTTs, ok_frac, ...) are deterministic per seed so a relative
# threshold is exact; the wall-clock-derived ratios (speedup,
# overhead_pct) are kept because same-machine ratios are stable, with an
# absolute floor on the *_pct family so near-zero values cannot flap.
_HIGHER_BETTER = ("mops", "ops_per_rtt", "batch_ops_per_rtt", "speedup",
                  "ok_frac", "reduction_transitions", "reduction_schedules")
_LOWER_BETTER = ("latency_us", "lat_p99_us", "ms", "scan_rtts",
                 "overhead_pct")
# row fields that identify a measurement (stable key parts)
_KEY_FIELDS = ("ycsb", "clients", "system", "shards", "mns", "r", "op",
               "batch", "step", "window", "mode", "alloc", "scope",
               "scan_len")
REGRESSION_REL = 0.10          # >10% move in the bad direction regresses
REGRESSION_PCT_FLOOR = 1.5     # *_pct metrics also need >1.5 points


def claim_metrics(rows):
    """Flatten bench rows into ``{stable-name: value}`` for the
    regression gate — only fields from the gated whitelists, keyed by the
    row's identifying fields so baselines survive row reordering."""
    out = {}
    for r in rows:
        bench = r.get("bench")
        if not bench:
            continue
        key = ".".join([str(bench)] + [f"{k}={r[k]}" for k in _KEY_FIELDS
                                       if r.get(k) is not None])
        for f in _HIGHER_BETTER + _LOWER_BETTER:
            v = r.get(f)
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[f"{key}.{f}"] = float(v)
    return out


def compare_baseline(current, baseline) -> bool:
    """Print a regression report; True when no gated metric moved >10%
    in its bad direction vs the baseline.  Metrics missing on either
    side (bench not run / newly added) are skipped, so ``--only`` runs
    gate just their own rows."""
    regressions, improved, checked = [], 0, 0
    for name in sorted(set(current) & set(baseline)):
        old, new = baseline[name], current[name]
        field = name.rsplit(".", 1)[-1]
        lower_better = field in _LOWER_BETTER
        delta = (new - old) if lower_better else (old - new)   # bad if > 0
        denom = max(abs(old), 1e-9)
        rel = delta / denom
        checked += 1
        bad = rel > REGRESSION_REL
        if field.endswith("_pct"):
            bad = bad and abs(delta) > REGRESSION_PCT_FLOOR
        if bad:
            regressions.append((name, old, new, rel))
        elif rel < -REGRESSION_REL:
            improved += 1
    print(f"\n== baseline comparison ({checked} metrics) ==")
    for name, old, new, rel in regressions:
        print(f"  [REGRESSED] {name}: {old:.4g} -> {new:.4g} "
              f"({100 * rel:+.1f}% worse)")
    if not regressions:
        print(f"  no regressions >{100 * REGRESSION_REL:.0f}% "
              f"({improved} metrics improved >10%)")
    skipped = len(set(current) - set(baseline))
    if skipped:
        print(f"  ({skipped} new metric(s) not in baseline — refresh it "
              f"with --claims-out)")
    return not regressions


def summarize(name: str, rows) -> str:
    if not rows:
        return "no-rows"
    if name == "trace_overhead":
        by = {r["mode"]: r for r in rows}
        return (f"fleet tick {by['off']['us_per_tick']:.0f}us/tick; "
                f"paused {by['paused']['overhead_pct']:+.1f}% "
                f"recording {by['recording']['overhead_pct']:+.1f}%")
    if name == "obs_overhead":
        by = {r["mode"]: r for r in rows}
        return (f"fleet tick {by['detached']['us_per_tick']:.0f}us/tick "
                f"detached; attached "
                f"{by['attached']['overhead_pct']:+.1f}% profiled "
                f"{by['profiled']['overhead_pct']:+.1f}%")
    if name == "explore_dpor":
        r = rows[0]
        return (f"{r['scope']}: dpor {r['dpor_states']} states/"
                f"{r['dpor_executions']} execs "
                f"({r['dpor_states_per_s']:.0f} states/s) vs naive "
                f"{r['naive_schedules']:.2e} schedules — "
                f"{r['reduction_transitions']:.0f}x transition reduction"
                f"{', deterministic' if r['deterministic'] else ''}")
    if name == "fig13_ycsb_scale":
        f = {(r["ycsb"], r["clients"], r["system"]): r["mops"] for r in rows}
        sp_c = f[("A", 128, "fusee")] / max(f[("A", 128, "clover")], 1e-9)
        sp_p = f[("A", 128, "fusee")] / max(f[("A", 128, "pdpm")], 1e-9)
        return (f"YCSB-A@128: fusee={f[('A', 128, 'fusee')]:.2f}Mops "
                f"{sp_c:.1f}x-clover {sp_p:.1f}x-pdpm")
    if name == "fig14_mn_scale":
        f = {(r["ycsb"], r["shards"], r["mns"]): r["mops"] for r in rows}
        s = f[("A", 8, 8)] / max(f[("A", 8, 2)], 1e-9)
        flat = f[("A", 1, 8)] / max(f[("A", 1, 2)], 1e-9)
        return (f"YCSB-A 2->8 MNs: S=8 {s:.1f}x scaling "
                f"(S=1 baseline {flat:.2f}x)")
    if name == "elastic_timeline":
        ev = {r["window"]: r for r in rows}
        worst = min((r["ok_frac"] for r in rows if r["ops_done"]),
                    default=0.0)
        return (f"{len(rows) - 1} windows, 2->4->3 MNs online; "
                f"min ok_frac {worst:.2f}, final migrations "
                f"{ev['final']['migrating_regions']}")
    if name == "tab1_recovery":
        t = {r["step"]: r for r in rows}
        return (f"total={t['total']['ms']:.1f}ms "
                f"reconnect={t['reconnect_mr']['pct']:.0f}% "
                f"traverse={t['traverse_log']['pct']:.1f}%")
    if name == "fig1819_replication":
        lat = {(r["r"], r["system"], r.get("op")): r.get("latency_us")
               for r in rows if r["bench"] == "fig19"}
        return (f"UPDATE r=1: fusee={lat.get((1, 'fusee', 'update'), 0):.1f}us"
                f" r=5: fusee={lat.get((5, 'fusee', 'update'), 0):.1f}us"
                f" cr={lat.get((5, 'fusee-cr', 'update'), 0):.1f}us")
    if name == "api_batch_search":
        best = max(rows, key=lambda r: r["batch"])
        return (f"batch SEARCH {best['batch_ops_per_rtt']:.0f} ops/RTT vs "
                f"serial {best['serial_ops_per_rtt']:.1f} "
                f"({best['speedup']:.1f}x at B={best['batch']})")
    if name == "ycsbe_scan":
        best = max(rows, key=lambda r: r["clients"])
        return (f"YCSB-E@{best['clients']}: {best['mops']:.2f}Mops "
                f"scan={best['scan_rtts']:.1f}RTTs "
                f"p99={best['lat_p99_us']:.0f}us")
    if name == "scan_batch":
        sp = [r for r in rows if r.get("speedup")]
        worst = min(sp, key=lambda r: r["speedup"])
        return (f"batched leaf sweep {worst['ops_per_rtt']:.1f} ops/RTT, "
                f"{worst['speedup']:.1f}x naive (len={worst['scan_len']})")
    if name == "roofline" and "arch" in rows[0]:
        worst = min(rows, key=lambda r: r.get("mfu_bound", 1))
        return (f"{len(rows)} cells; worst MFU-bound "
                f"{worst['arch']}/{worst['shape']}={worst['mfu_bound']:.3f}")
    if name == "roofline" and rows[0].get("mode") == "fleet-tick":
        top = max(rows, key=lambda r: r["clients"])
        return (f"fleet tick @{top['clients']}: "
                f"{top['t_fused_ms_per_tick']:.2f}ms fused vs "
                f"{top['t_unfused_ms_per_tick']:.2f}ms oracle "
                f"({top['speedup']:.1f}x, {top['gbps_fused']:.2f}GB/s)")
    return f"{len(rows)} rows"


def validate_claims(rows):
    """§Paper-claims quick checks (full narrative in EXPERIMENTS.md)."""
    checks = []
    f13 = {(r.get("ycsb"), r.get("clients"), r.get("system")): r["mops"]
           for r in rows if r.get("bench") == "fig13"}
    if f13:
        sp = f13[("A", 128, "fusee")] / max(f13[("A", 128, "clover")], 1e-9)
        checks.append(("fusee >= 4x clover @128 clients (paper: 4.9x)",
                       sp >= 4.0, f"{sp:.1f}x"))
        spp = f13[("A", 128, "fusee")] / max(f13[("A", 128, "pdpm")], 1e-9)
        checks.append(("fusee >> pdpm @128 clients (paper: 117x)",
                       spp >= 20.0, f"{spp:.0f}x"))
    f14 = {(r.get("ycsb"), r.get("shards"), r.get("mns")): r["mops"]
           for r in rows if r.get("bench") == "fig14"}
    if f14:
        sp = f14[("A", 8, 8)] / max(f14[("A", 8, 2)], 1e-9)
        checks.append(("sharded index scales with MNs (>=1.5x, 2->8 MNs, S=8)",
                       sp >= 1.5, f"{sp:.1f}x"))
    el = [r for r in rows if r.get("bench") == "elastic"
          and r.get("window") != "final"]
    if el:
        alive = min((r["ok_frac"] for r in el if r["ops_done"]),
                    default=0.0)
        fin = [r for r in rows if r.get("bench") == "elastic"
               and r.get("window") == "final"]
        checks.append(("store stays available through add/remove MN",
                       alive > 0.9 and bool(fin)
                       and all(r["ops_done"] > 0 for r in el)
                       and fin[0]["migrating_regions"] == 0,
                       f"min ok_frac {alive:.2f}"))
    f19 = [(r["r"], r["system"], r["latency_us"]) for r in rows
           if r.get("bench") == "fig19" and r.get("op") == "update"]
    if f19:
        fus = {r: l for r, s, l in f19 if s == "fusee"}
        cr = {r: l for r, s, l in f19 if s == "fusee-cr"}
        flat = fus[5] / fus[1]
        lin = cr[5] / cr[1]
        checks.append(("SNAPSHOT latency ~flat in r; CR grows linearly",
                       flat < 1.8 and lin > 2.0,
                       f"fusee x{flat:.2f}, cr x{lin:.2f} from r=1->5"))
    t1 = {r["step"]: r for r in rows if r.get("bench") == "tab1"}
    if t1:
        checks.append(("recovery dominated by reconnect (paper: 92%)",
                       t1["reconnect_mr"]["pct"] > 80,
                       f"{t1['reconnect_mr']['pct']:.0f}%"))
    ab = [r for r in rows if r.get("bench") == "api_batch"]
    if ab:
        worst = min(r["speedup"] for r in ab)
        checks.append(("batched SEARCH beats serial ops/RTT at every size",
                       worst > 1.0, f"min speedup {worst:.1f}x"))
    sb = [r for r in rows if r.get("bench") == "scan_batch"
          and r.get("speedup")]
    if sb:
        worst = min(r["speedup"] for r in sb)
        checks.append(("batched leaf traversal >= 4x naive per-slot ops/RTT",
                       worst >= 4.0, f"min speedup {worst:.1f}x"))
    ye = [r for r in rows if r.get("bench") == "ycsbe"]
    if ye:
        ok = all(r["sim_ops"] > 0 and r["mops"] > 0 for r in ye)
        checks.append(("YCSB-E runs end to end on the fleet engine",
                       ok, f"{max(r['mops'] for r in ye):.2f} Mops"))
    f17 = {r["alloc"]: r["mops"] for r in rows
           if r.get("bench") == "fig17" and r.get("ycsb") == "A"}
    if f17:
        drop = 1 - f17["mn-centric"] / f17["two-level"]
        checks.append(("MN-centric alloc collapses under YCSB-A (paper: -90.9%)",
                       drop > 0.5, f"-{100 * drop:.0f}%"))
    to = {r["mode"]: r for r in rows if r.get("bench") == "trace_overhead"}
    if to:
        ov = to["paused"]["overhead_pct"]
        checks.append(("tracer disabled-mode overhead on fleet ticks < 3%",
                       ov < 3.0,
                       f"paused {ov:+.1f}%, recording "
                       f"{to['recording']['overhead_pct']:+.1f}%"))
    oo = {r["mode"]: r for r in rows if r.get("bench") == "obs_overhead"}
    if oo:
        ov = oo["attached"]["overhead_pct"]
        checks.append(("attached obs hub overhead on fleet ticks < 5%",
                       ov < 5.0, f"attached {ov:+.1f}%"))
        op = oo["profiled"]["overhead_pct"]
        checks.append(("hub + hot-key monitor (profiled) overhead < 5%",
                       op < 5.0, f"profiled {op:+.1f}%"))
    rl = [r for r in rows if r.get("bench") == "roofline"
          and r.get("mode") == "fleet-tick"]
    if rl:
        top = max(rl, key=lambda r: r["clients"])
        checks.append(("fused tick >= 3x per-kind oracle @1024 clients",
                       top["clients"] >= 1024 and top["speedup"] >= 3.0,
                       f"{top['speedup']:.1f}x at {top['clients']} clients "
                       f"({top['t_unfused_ms_per_tick']:.2f} -> "
                       f"{top['t_fused_ms_per_tick']:.2f} ms/tick)"))
    exp = [r for r in rows if r.get("bench") == "explore"]
    if exp:
        r = exp[0]
        checks.append(("DPOR prunes >= 5x vs naive enumeration "
                       "(insert-race scope)",
                       r["reduction_transitions"] >= 5.0
                       and r["reduction_schedules"] >= 5.0,
                       f"{r['reduction_transitions']:.0f}x transitions, "
                       f"{r['reduction_schedules']:.0f}x schedules "
                       f"({r['dpor_work']} fired vs "
                       f"{r['naive_transitions']:.2e} naive)"))
        checks.append(("exploration bit-identical across repeat runs",
                       bool(r["deterministic"]),
                       f"digest {r['visit_digest'][:16]}"))
        checks.append(("dpor finds no violations on the clean scope",
                       r["dpor_states"] > 0 and r["dedup_states"] > 0
                       and r["dpor_states"] <= r["dedup_states"],
                       f"{r['dpor_states']}/{r['dedup_states']} states"))
    print("\n== paper-claims validation ==")
    ok = True
    for name, passed, detail in checks:
        print(f"  [{'PASS' if passed else 'FAIL'}] {name}: {detail}")
        ok &= passed
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
