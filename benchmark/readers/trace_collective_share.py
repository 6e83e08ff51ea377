"""Collective operations' device time over device-busy time on the first
chip, in percent (names in `xplane.COLLECTIVE_WORDS`). Nothing to read on
one chip."""


def read(ctx, params):
    red = ctx.trace_reduction
    if not red or red["devices"] < 2 or red["first_device_busy_s"] <= 0:
        return None
    return 100.0 * red["collective_s"] / red["first_device_busy_s"]
