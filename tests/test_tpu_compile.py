"""Compiles of the device store's kernels and pool step for a described
TPU v5e, at the sizes ``chip_smoke.py`` runs: no chip is needed, and the
chip's compiler refuses here what it would refuse there (tiling, fast
memory, layouts).  A compile that passes is not a chip run.

Every compile test lives in this file: the topology is described in a
module fixture, never at import, so that only the test worker given this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.leaf_probe.kernel import leaf_probe_fwd
from repro.kernels.race_lookup.kernel import race_lookup_fwd
from repro.core.codec import KEY_WORDS
from repro.serving import kvpool
from repro.serving.snapshot_jax import snapshot_epoch

N_BUCKETS, SPB, REPLICAS, BATCH = 65536, 8, 3, 4096
N_KEYS = 100_000


@pytest.fixture(scope="module")
def shape():
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described v5e
    chip; the persistent compilation cache is off meanwhile (a compile
    for a described chip cannot be read back from it)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        chip = SingleDeviceSharding(topo.devices[0])
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield lambda dims, dt: jax.ShapeDtypeStruct(dims, dt,
                                                        sharding=chip)
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            cc.reset_cache()


@pytest.mark.parametrize("n_buckets", [32768, N_BUCKETS])
def test_race_lookup_compiles_for_v5e(shape, n_buckets):
    compiled = jax.jit(
        lambda k, i: race_lookup_fwd(k, i, interpret=False)).lower(
        shape((BATCH,), jnp.int32),
        shape((n_buckets, SPB), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_leaf_probe_compiles_for_v5e(shape):
    """One scan start against the whole keydir as the fence table."""
    compiled = jax.jit(
        lambda *a: leaf_probe_fwd(*a, interpret=False)).lower(
        shape((256,), jnp.uint32), shape((256,), jnp.uint32),
        shape((N_KEYS,), jnp.uint32), shape((N_KEYS,), jnp.uint32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_snapshot_epoch_compiles_for_v5e(shape):
    i32 = jnp.int32
    compiled = snapshot_epoch.lower(
        shape((REPLICAS, N_BUCKETS * SPB), i32), shape((BATCH,), i32),
        shape((BATCH,), i32), shape((BATCH,), i32),
        shape((2,), jnp.uint32)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes >= REPLICAS * N_BUCKETS * SPB * 4


# the write path's jitted steps at the largest cell's pool (1M records:
# 2^20 buckets x 8, 2^21 pages, r = 3), for the window's padded UPDATE
# batch and the load's
POOL_BUCKETS, POOL_PAGES = 1 << 20, 1 << 21


@pytest.mark.parametrize("step", ["write", "prepare", "select", "commit"])
@pytest.mark.parametrize("width", [256, 16384])
def test_write_path_steps_compile_for_v5e(shape, step, width):
    i32 = jnp.int32
    log = shape((POOL_PAGES, 4), i32)
    page_keys = shape((POOL_PAGES, KEY_WORDS), i32)
    vec = shape((width,), i32)
    keys = shape((width, kvpool.KEY_COLS), i32)
    if step == "write":
        lowered = kvpool._write_step.lower(
            log, shape((POOL_PAGES, 2), i32), page_keys, vec,
            shape((width, 4), i32), shape((width, 2), i32),
            shape((width, KEY_WORDS), i32))
    elif step == "prepare":
        lowered = kvpool._upsert_prepare.lower(keys, vec)
    elif step == "select":
        lowered = kvpool._upsert_select.lower(
            shape((REPLICAS, POOL_BUCKETS * SPB), i32), page_keys, keys,
            shape((width,), jnp.bool_), shape((2,), jnp.uint32),
            slots_per_bucket=SPB)
    else:
        lowered = kvpool._upsert_commit.lower(
            log, shape((POOL_PAGES,), jnp.int8), shape((width,), jnp.bool_),
            vec, vec)
    mem = lowered.compile().memory_analysis()
    # no step copies the O(M) index: select reads its rows in place
    assert mem.temp_size_in_bytes < REPLICAS * POOL_BUCKETS * SPB * 4


@pytest.mark.parametrize("step", ["check", "all_check"])
@pytest.mark.parametrize("width", [256, 4096])
def test_search_key_checks_compile_for_v5e(shape, step, width):
    """The lookup's key checks at the 1M-record pool: neither copies the
    index, the log or the page keys, and each returns one word a key."""
    i32 = jnp.int32
    log = shape((POOL_PAGES, 4), i32)
    page_keys = shape((POOL_PAGES, KEY_WORDS), i32)
    keys = shape((width, kvpool.KEY_COLS), i32)
    if step == "check":
        lowered = kvpool._search_check.lower(
            log, page_keys, keys, shape((width,), i32),
            shape((width,), jnp.bool_))
    else:
        lowered = kvpool._search_all_check.lower(
            shape((REPLICAS, POOL_BUCKETS * SPB), i32), log, page_keys, keys,
            slots_per_bucket=SPB)
    mem = lowered.compile().memory_analysis()
    assert mem.output_size_in_bytes == width * 4
    assert mem.temp_size_in_bytes < POOL_PAGES * 4
