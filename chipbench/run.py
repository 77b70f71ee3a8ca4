"""The on-chip benchmark of the FUSEE device store: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs the cell of ``BENCHMARK.json`` named by ``--workload`` on the TPU that
this process finds, through ``KVStore.submit_batch`` over ``DeviceBackend``
(``harness.py`` says how).  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the profiler traces the
window and the result carries the per-layer metrics and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (as JAX reports it), with
``--trace 1`` a ``breakdown``, and last ``checks``, each number that
decided ``correct`` beside its limit.  The same checks are the last lines
of standard error.  Exits 2 and prints no result where JAX finds no TPU,
fewer chips than the cell asks for, a device missing from ``peaks.json``,
or no program (``src/repro``) beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number of 0 or more")

    if not (REPO / "src" / "repro").is_dir():
        log(f"chipbench: the program is not beside the benchmark "
            f"(no {REPO / 'src' / 'repro'})")
        return 2
    for p in (str(REPO / "src"), str(REPO)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness
    try:
        cell = harness.resolve(args.workload)
    except KeyError as e:
        log(f"chipbench: {e.args[0]}")
        return 2

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"chipbench: needs a TPU, but JAX's first device is on "
            f"{devices[0].platform!r}; no result")
        return 2
    if len(devices) < cell.chips:
        log(f"chipbench: {cell.name} needs {cell.chips} chips, JAX finds "
            f"{len(devices)}; no result")
        return 2
    try:
        harness.peaks_for(devices[0].device_kind)
    except KeyError as e:
        log(f"chipbench: {e.args[0]}; no result")
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START, log=log)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
