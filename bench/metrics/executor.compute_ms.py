"""Busy time of the executor's per-device compute threads (the fabric driver's
``run``: upload, device loop, download, compaction), per sweep.

The program's ``SYNC_STATS["compute_wall_s"]`` over the window, summed
over devices."""


def read(run):
    if not run["sweeps"]:
        return None
    return 1e3 * run["sync"]["compute_wall_s"] / run["sweeps"]
