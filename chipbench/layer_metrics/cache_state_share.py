"""Cache manager: when the K/V pool was at its fullest
(`kv_blocks_at_high_water`: K/V blocks and state slots in use at that
moment), bytes held in state slots over bytes held in slots and K/V blocks
together (the family's `cache_state_share`), %: how much of what sequences
hold does not grow with their length."""


def read(ctx):
    held = ctx.counters.get("kv_blocks_at_high_water")
    share = getattr(ctx.family, "cache_state_share", None)
    if share is None or not held or "state_num_slots" not in ctx.counters \
            or not held[0]:
        return None
    return share(ctx.cell.config, held[0], held[-1],
                 ctx.counters["block_size"])
