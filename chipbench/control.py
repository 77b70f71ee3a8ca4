"""Sound runs and planted faults of one cell, on many seeds, in one process.

    python3 chipbench/control.py --workload <cell> \
        --runs 11:none,11:unreplicated,12:unreplicated --seconds 5
    python3 chipbench/control.py --config <config> --traffic <mix> \
        --runs 11:none,12:none

Each ``seed:fault`` pair is a whole run of ``harness.run_cell`` at the
cell's own size and load: set-up, a window of ``--seconds``, the check.
``none`` is the program as it is.  ``--config`` and ``--traffic`` name a
configuration file and a mix file instead of a cell of ``BENCHMARK.json``:
that is how a deployment left out of it shows why.  One JSON line per run gives the numbers
that decide ``correct`` (the readings that the limits are set from), and
``ops_per_s``.  The benchmark's own runs never run this; it is how the
limits in ``PERF.md`` were read.  It needs a TPU, as ``run.py`` does.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--runs", required=True,
                    help="seed:fault pairs, comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    if (args.workload is None) == (args.config is None or
                                   args.traffic is None):
        ap.error("give --workload, or --config and --traffic")
    for p in (str(REPO / "src"), str(REPO)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import faults, harness

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = (harness.resolve(args.workload) if args.workload else
            harness.cell_of(args.config, args.traffic))
    t = T_START
    for run in args.runs.split(","):
        seed, fault = run.split(":")
        undo = faults.install(fault) if fault != "none" else None
        try:
            out = harness.run_cell(
                cell, int(seed), args.seconds, False, t_start=t,
                log=lambda m: print(m, file=sys.stderr, flush=True))
        finally:
            if undo is not None:
                undo()
        print(json.dumps({
            "workload": cell.name, "seed": int(seed), "fault": fault,
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"],
            "ops_per_s": out["metrics"].get("ops_per_s", {}).get("value"),
            "setup_s": out["metrics"].get("setup_s", {}).get("value"),
            "checks": {k: c["value"] for k, c in out["checks"].items()},
        }), flush=True)
        gc.collect()
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
