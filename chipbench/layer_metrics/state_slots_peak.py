"""Cache manager: the state pool's high-water mark over its slots, %. A
sequence holds one slot however long it runs; with `kv_blocks_peak` it says
which kind binds admission."""


def read(ctx):
    c = ctx.counters
    if "state_high_water_slots" not in c:
        return None
    return 100.0 * c["state_high_water_slots"] / c["state_num_slots"]
