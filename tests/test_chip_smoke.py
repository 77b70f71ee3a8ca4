"""The chip smoke's store phases, at a tiny size on the CPU, and its
refusal to run anywhere but on a TPU."""
import os
import subprocess
import sys
from pathlib import Path

from benchmarks.store_phases import run_store_phases
from repro.core.api import KVStore
from repro.serving import DeviceBackend, PoolConfig

ROOT = Path(__file__).resolve().parents[1]


def test_store_phases_match_dict_reference():
    """Every phase of chip_smoke.py (interpret-mode kernels here) gives
    the dict reference's answers, with no FULL and converged replicas."""
    backend = DeviceBackend(PoolConfig(n_pages=1024, n_buckets=128,
                                       slots_per_bucket=8, replicas=3,
                                       chunk_pages=16, n_shards=4), seed=1)
    lines = []
    reports = run_store_phases(KVStore(backend), n_keys=256, value_bytes=1024,
                               theta=0.99, batch=64, read_batches=2,
                               mixed_batches=4, n_delete=32, n_scans=4,
                               seed=3, log=lines.append)
    assert [r.name for r in reports] == ["load", "ycsb_c", "ycsb_a",
                                         "delete", "scan"]
    for r in reports:
        assert r.ok and r.ops > 0, (r, lines)
    assert reports[0].ops == 256
    assert reports[3].ops == 64            # 32 DELETEs, then 32 GETs


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=120, cwd=ROOT)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout == ""
