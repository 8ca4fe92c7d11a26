"""Iterations of the device ``while_loop``, summed over its rounds, per
sweep: with ``loop.device_rounds`` it says whether rounds end at the
program's round cap, and whether a kernel change made each iteration
cheaper or cut their number. The program's counter
``SYNC_STATS["iterations"]`` over the window; a program without that
counter reads nothing."""


def read(run):
    iterations = run["sync"].get("iterations")
    if iterations is None or not run["sweeps"]:
        return None
    return iterations / run["sweeps"]
