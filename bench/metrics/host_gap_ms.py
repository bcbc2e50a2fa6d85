"""Per call, the wall time of the benchmark's span around the call minus the
device-busy time inside it, on the profiler's clock and averaged over the
chips: how long the device waited on the host (the facade, the dispatch
ladder, the enqueue of each program) in one call, in ms."""


def read(run):
    tr = run.trace
    if tr is None or not tr.calls or not tr.ops:
        return None
    busy = tr.call_busy_s()
    gaps = [(c.end - c.start) / 1e9 - b for c, b in zip(tr.calls, busy)]
    return sum(gaps) / len(gaps) * 1e3
