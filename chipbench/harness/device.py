"""The device a run is on: found or refused, its peaks, its memory."""
import json
import os


class NoAccelerator(SystemExit):
    """Raised (exit code 3) where jax finds no TPU or too few chips."""


def require(chips):
    """The first `chips` TPU devices, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            "chipbench: jax found no TPU (platform %r); nothing was measured"
            % devs[0].platform)
    if len(devs) < chips:
        raise NoAccelerator("chipbench: the cell asks for %d chips, jax has %d"
                            % (chips, len(devs)))
    return devs[:chips]


def describe(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_stats(devices):
    """The runtime's own byte counters of each chip, for an earlier line, and
    what they add up to now (`footprint_bytes`: arrays and program scratch)."""
    out = []
    for d in devices:
        s = {k: v for k, v in (d.memory_stats() or {}).items() if "bytes" in k}
        s["footprint_bytes"] = s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
        out.append(s)
    return out


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of the chips used: the sum of the runtime's
    two peaks. `bytes_in_use` counts the arrays a process holds and
    `bytes_reserved` the scratch of the programs it has loaded; they are
    disjoint (the runtime's own `largest_free_block_bytes` is `bytes_limit`
    less both, to within a megabyte, in every probe and run of PR 23: a
    program with a 4.3 GB temporary over a 0.5 GB argument reads 0.54 and
    4.295 GB), and a reservation stays once its program is loaded
    (`bytes_reserved` equals its peak at the close of every window). So from
    the end of warm-up the reservation is at its peak whenever the arrays
    are, and the sum overstates only by arrays that set-up alone held above
    anything the window holds; the earlier line gives the footprint at the
    window's close beside it."""
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0)
                   for s in memory_stats(devices)))


def peaks(device_kind):
    """Published peaks of one chip of this kind. An unknown kind is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks recorded for device_kind %r; add it to "
                       "chipbench/harness/peaks.json with its source"
                       % device_kind)
    return table[device_kind]
