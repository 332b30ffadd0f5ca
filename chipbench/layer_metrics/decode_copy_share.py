"""Engine step, serving: device time in plain `copy` operations (XLA's own:
an operand moved to another layout or buffer; a step that updates the KV
pools in place has none of the pool's size) over the device time of the
programs run in the traced slice, %. Device 0's "XLA Ops" line by short
name (`trace/reduce.py`, `ops`): `copy` and `copy.<n>` count, a fusion that
has "copy" in its name does not."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["modules"]:
        return None
    copies = sum(seconds for name, seconds in ctx.trace["ops"].items()
                 if name == "copy" or name.startswith("copy."))
    return 100.0 * copies / sum(d for _, d, _ in ctx.trace["modules"])
