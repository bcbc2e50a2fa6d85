"""The share of the HBM roofline a call reaches, in %: the least time the
chips could take, the call's necessary bytes (every key and value read once
and written once, from n and the element widths alone) over the peak HBM
bandwidth of all its chips, divided by the device-busy time per call (mean
over calls and chips). The peak is the device kind's in bench/peaks.json."""

import numpy as np

from bench.reference import necessary_bytes


def _itemsize(dtype):
    return 0 if dtype is None else np.dtype(dtype).itemsize


def read(run):
    tr = run.trace
    if tr is None or not tr.calls or not tr.ops:
        return None
    busy = tr.call_busy_s()
    per_call = sum(busy) / len(busy)
    if per_call <= 0:
        return None
    cfg = run.cell.cfg
    need = necessary_bytes(int(cfg["n"]), _itemsize(cfg["key_dtype"]),
                           _itemsize(cfg.get("value_dtype")))
    least_s = need / (float(run.peak["hbm_bytes_per_s"]) * run.cell.chips)
    return 100.0 * least_s / per_call
