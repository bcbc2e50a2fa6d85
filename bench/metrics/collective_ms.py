"""Per call, the device time of the ops that move data between chips
(all-to-all, all-gather, all-reduce, collective-permute and their async
forms, found by HLO opcode) on the slowest chip, in ms. Nothing to read
where no collective ran."""


def read(run):
    tr = run.trace
    if tr is None or not tr.calls or not tr.ops:
        return None
    per_call = tr.collective_s_per_call()
    return None if per_call is None else per_call * 1e3
