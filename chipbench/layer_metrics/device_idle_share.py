"""Device: 1 - (union of device-op intervals) / traced slice, %. One reader
for `device_idle_share.train` and `device_idle_share.serve`: the manifest
says which cells report which."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
