"""Host time building chunks (runner and plan ingest), per sweep.

The program's ``SYNC_STATS["build_wall_s"]`` over the window, summed
over its prep threads; it overlaps device compute."""


def read(run):
    if not run["sweeps"]:
        return None
    return 1e3 * run["sync"]["build_wall_s"] / run["sweeps"]
