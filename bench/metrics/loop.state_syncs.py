"""Whole-state copies of the device loop back to the host, per sweep:
before a parked row's replay, a compaction, an error report and at the
end of each driver run. Between them the loop state stays on the device
and each round reads back only its flags, so ``loop.device_rounds``
less this is the rounds that resumed from the device. The program's
counter ``SYNC_STATS["state_syncs"]`` over the window; a program without
that counter reads nothing."""


def read(run):
    syncs = run["sync"].get("state_syncs")
    if syncs is None or not run["sweeps"]:
        return None
    return syncs / run["sweeps"]
