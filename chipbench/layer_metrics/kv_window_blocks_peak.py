"""Cache manager: the window layers' pool's high-water mark over its blocks,
%. A sequence holds a ring of them at most (window / block size + 1),
however long it runs."""


def read(ctx):
    c = ctx.counters
    if "kv_window_high_water_blocks" not in c:
        return None
    return 100.0 * c["kv_window_high_water_blocks"] / c["kv_window_num_blocks"]
