"""The share of the traced window, from the first call's start to the last
call's end, in which no op ran on the device, mean over chips, in %."""


def read(run):
    tr = run.trace
    if tr is None or not tr.calls or not tr.ops:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s())
