"""A cell small enough for the CPU: the tests' stand-in for a real one."""
from __future__ import annotations

import json
import sys
import time

from chipbench import harness

CONFIG = {"records": 2000, "value_bytes": 16, "load_batch": 1000,
          "pool": {"n_pages": 2560, "n_buckets": 1024, "slots_per_bucket": 8,
                   "replicas": 3, "chunk_pages": 64, "n_shards": 4}}


def cell(traffic: str = "ycsb-a", clients: int = 64,
         key_format: str = "int") -> harness.Cell:
    mix = json.loads((harness.HERE / "mixes" / f"{traffic}.json").read_text())
    mix["clients"] = clients
    bench = harness.benchmark()
    return harness.Cell(f"tiny.{traffic}",
                        dict(CONFIG, key_format=key_format), mix, 1,
                        bench["end_to_end"], bench["per_layer"])


def run(c: harness.Cell, seed: int, seconds: float = 0.5) -> dict:
    return harness.run_cell(c, seed, seconds, False,
                            t_start=time.perf_counter(),
                            log=lambda m: print(m, file=sys.stderr))
