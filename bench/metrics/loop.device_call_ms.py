"""Host time of the device loop's calls, per sweep: from each round's
call into the compiled ``while_loop`` until its outputs are ready, summed
over rounds and devices. The program's span ``fabric.device``
(``SYNC_STATS["device_wall_s"]``) over the window. It is a host clock
around the call, so it holds the launch and the return besides the
device's own time; a program without that span reads nothing."""


def read(run):
    device_s = run["sync"].get("device_wall_s")
    if device_s is None or not run["sweeps"]:
        return None
    return 1e3 * device_s / run["sweeps"]
