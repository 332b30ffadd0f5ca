"""Expert layer: over the window, the rows of the busiest (expert layer, held
expert) over the mean of all of them: the straggler a grouped product waits
for. From the family's counter `moe_expert_tokens_window` (the program's
`expert_rows` tally at the window's end less at its start, prefill and
decode)."""


def read(ctx):
    rows = [n for layer in ctx.counters.get("moe_expert_tokens_window") or []
            for n in layer]
    if not rows or not sum(rows):
        return None
    return max(rows) * len(rows) / sum(rows)
