"""Host time copying each device round's ready outputs back, per sweep:
transfer and host copy, the device's own time excluded. The program's
span ``fabric.download`` (``SYNC_STATS["download_wall_s"]``) over the
window. A program without ``device_wall_s`` does not wait for the device
before that span, so its key holds the device's time too: it reads
nothing."""


def read(run):
    sync = run["sync"]
    if "device_wall_s" not in sync or not run["sweeps"]:
        return None
    return 1e3 * sync["download_wall_s"] / run["sweeps"]
