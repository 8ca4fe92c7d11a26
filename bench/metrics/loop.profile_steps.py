"""Bandwidth-profile steps that fell inside the transfers, per sweep:
for each row, the steps of its path's capacity profile after t = 0 and
no later than the row's finish time, summed over rows. It says whether
capacity really changed under the transfers the cell times; a static
path adds 0. The program's counter ``SYNC_STATS["profile_steps"]`` over
the window; a program without that counter reads nothing."""


def read(run):
    steps = run["sync"].get("profile_steps")
    if steps is None or not run["sweeps"]:
        return None
    return steps / run["sweeps"]
