"""Share of the traced slice (``trace.SLICE_S`` seconds in the middle of
one sweep) in which no operation ran on the device: 1 minus the union
of the device's op intervals over the slice, averaged over the chips
used."""


def read(run):
    tr = run["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
