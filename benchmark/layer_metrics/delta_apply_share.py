"""storage to device: of the times a read of the window found its
resident relation behind the newest commit, the share it brought up by
applying the committed delta (``storage.delta_applies``) and not by a
rebuild (``storage.device_copy_builds``).  100 when no read of the window
rebuilt.  ``None`` when neither happened in the window, or the program has
no such counter."""

APPLIES = "storage.delta_applies"
BUILDS = "storage.device_copy_builds"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    if APPLIES not in after:
        return None
    applies = after[APPLIES] - before.get(APPLIES, 0.0)
    builds = after.get(BUILDS, 0.0) - before.get(BUILDS, 0.0)
    if applies + builds <= 0:
        return None
    return 100.0 * applies / (applies + builds)
