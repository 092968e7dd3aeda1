"""Share of the traced window in which no operation ran on the device,
in percent: 1 - (union of device-op intervals / window)."""


def read(run):
    if run.device_busy_s is None or not run.device_window_s:
        return None
    return 100.0 * (1.0 - run.device_busy_s / run.device_window_s)
