"""Share of its memory roofline that ``jit_snapshot_epoch`` reaches: the
bytes each epoch of the window needs for its active writers
(``costs/snapshot_epoch.py``) at the chip's HBM bandwidth, over the
device time of the module's events."""


def read(r):
    t = r.trace.module_seconds("jit_snapshot_epoch")
    if not r.epoch_writers or t <= 0:
        return None
    cost = r.cost("snapshot_epoch")
    pool = r.cell.config["pool"]
    need = sum(cost.bytes_needed(w, pool) for w in r.epoch_writers)
    return 100.0 * need / r.peaks["hbm_bytes_per_s"] / t
