"""Share of the traced window in which no operation ran on the device,
in percent (1 - busy / window, busy being the union of operation
intervals, averaged over the chips).  Layer: the device."""


def read(r):
    if r.device is None or r.device.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.device.busy_s / r.device.window_s)
