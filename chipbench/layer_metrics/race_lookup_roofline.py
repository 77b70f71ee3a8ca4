"""Share of its memory roofline that ``jit_race_lookup`` (the bucket-row
gather and the Pallas probe) reaches: the bytes the probes of the window
need (``costs/race_lookup.py``, from the keys of each GET batch) at the
chip's HBM bandwidth, over the device time of the module's events."""


def read(r):
    sizes = r.spans.sizes.get("cb.pool.search", [])
    t = r.trace.module_seconds("jit_race_lookup")
    if not sizes or t <= 0:
        return None
    cost = r.cost("race_lookup")
    pool = r.cell.config["pool"]
    need = sum(cost.bytes_needed(n, pool) for n in sizes)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
