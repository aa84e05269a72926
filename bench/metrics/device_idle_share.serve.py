"""Share of the traced window in which no operation ran on the device."""


def read(ctx, res):
    tr = res.get("trace")
    if tr is None or not tr.devices or tr.window_ns <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns() / tr.window_ns)
