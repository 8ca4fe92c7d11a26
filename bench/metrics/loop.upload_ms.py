"""Host time putting each device round's inputs on the device (building
the round's buffers and enqueueing them), per sweep. The program's span
``fabric.upload`` (``SYNC_STATS["upload_wall_s"]``) over the window; a
program without that span reads nothing."""


def read(run):
    upload_s = run["sync"].get("upload_wall_s")
    if upload_s is None or not run["sweeps"]:
        return None
    return 1e3 * upload_s / run["sweeps"]
