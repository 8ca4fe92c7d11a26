"""Share of the device loop's channel axis that no row can use, in %:
100 x (1 - ``channel_slots_live`` / ``channel_slots``), where
``channel_slots`` is each driver run's real rows times its padded
channel axis C and ``channel_slots_live`` the most channels each of
those rows can hold, summed. Every loop kernel carries the whole axis.
The program's counters in ``SYNC_STATS`` over the window; a program
without them reads nothing."""


def read(run):
    slots = run["sync"].get("channel_slots")
    live = run["sync"].get("channel_slots_live")
    if not slots or live is None:
        return None
    return 100.0 * (1.0 - live / slots)
