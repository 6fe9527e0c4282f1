"""Share of the window in which no operation ran on the device, in %."""


def read(rec):
    return 100.0 * (1.0 - rec.busy_s / rec.window_s) if rec.busy_s else None
