"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of device-operation intervals / window), in percent.
One reader for every cell: ``device_idle_share.dedup`` and
``device_idle_share.match`` are this quantity split by the rate each
moves."""


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or not t.devices:
        return None
    return 100.0 * t.idle_share
