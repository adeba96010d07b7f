"""Share of the traced window in which no operation ran on the device:
1 - busy / window, busy being the union of the operation intervals.
Layer: device (TPU v5e)."""


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 1.0 - tr.busy_s / tr.window_s
