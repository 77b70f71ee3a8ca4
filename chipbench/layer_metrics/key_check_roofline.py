"""Share of its memory roofline that the lookup's full-key check reaches:
the bytes the window's searched and shadowed keys need
(``costs/key_check.py``; the keys of each GET batch and the program's
counter ``pool.search.shadowed``) at the chip's HBM bandwidth, over the
device time of the ``jit__search_check`` and ``jit__search_all_check``
modules' events."""

MODULES = ("jit__search_check", "jit__search_all_check")


def read(r):
    sizes = r.spans.sizes.get("cb.pool.search", [])
    t = sum(r.trace.module_seconds(m) for m in MODULES)
    if not sizes or t <= 0:
        return None
    cost = r.cost("key_check")
    need = cost.bytes_needed(sum(sizes), r.cell.config["pool"],
                             r.counters.get("pool.search.shadowed", 0))
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
