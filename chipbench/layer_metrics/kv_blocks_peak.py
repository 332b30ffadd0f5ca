"""Cache manager: the pool's high-water mark over its blocks, %."""


def read(ctx):
    c = ctx.counters
    if "kv_high_water_blocks" not in c:
        return None
    return 100.0 * c["kv_high_water_blocks"] / c["kv_num_blocks"]
