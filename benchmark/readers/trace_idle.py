"""The device's idle share of the traced window, in percent: 1 - (union of
the device-operation intervals) / (first annotated update to the end of the
closing fetch), mean over the devices."""


def read(name, args, run):
    tr = run["trace"]
    if tr is None or not tr.devices or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns() / tr.window_ns)
