"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window's
host-clock length, averaged over the chips used."""


def read(rec):
    if rec.trace is None or not rec.trace.devices or rec.window_s <= 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.window_s)
