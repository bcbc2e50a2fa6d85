"""Keys (pairs, in a key-value cell; of all chips, on several) completed in
the window, over the wall time from the first call's start to the last
call's end: all the work over all the time, in Gkeys/s."""


def read(run):
    if run.window_s <= 0 or not run.completed:
        return None
    return run.completed * int(run.cell.cfg["n"]) / run.window_s / 1e9
