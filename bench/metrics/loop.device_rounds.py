"""Entries into the device ``while_loop`` per sweep (compaction rungs and
straggler re-entries included): each is an upload, a device call and a
download. The program's ``SYNC_STATS["rounds"]`` over the window."""


def read(run):
    if not run["sweeps"]:
        return None
    return run["sync"]["rounds"] / run["sweeps"]
