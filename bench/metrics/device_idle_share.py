"""device_idle_share (layer: device): 100 x (1 - the union of the
device-operation intervals in the traced window over the window).
Moves itl_p95_ms."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
