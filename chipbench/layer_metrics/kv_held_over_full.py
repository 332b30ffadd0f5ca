"""Cache manager: bytes both pools held when the full layers' pool was at its
fullest (`kv_blocks_at_high_water`: blocks in use by kind at that moment) over
the bytes the same sequences would hold if every layer kept every token
(the family's `held_over_full`), %. 100 for a program that keeps every token
in every layer."""


def read(ctx):
    held = ctx.counters.get("kv_blocks_at_high_water")
    ratio = getattr(ctx.family, "held_over_full", None)
    if ratio is None or not held or not held[0]:
        return None
    return ratio(ctx.cell.config, *held)
