"""The share of the interconnect roofline the exchange reaches, in %: the
least time the chips could take to move the pairs that must change chips,
over the collective time per call on the slowest chip.

The bytes that must cross are computed from n, the chip count and the
element widths alone: under uniform keys each pair leaves its chip with
probability (D-1)/D. No transport, padding or position shipped with the
data moves this yardstick. The least time is those bytes over the peak
interconnect bandwidth of all the chips (``ici_bits_per_s`` of the device
kind in bench/peaks.json). Nothing to read on one chip, or where no
collective ran."""

import numpy as np


def cross_chip_bytes(n: int, chips: int, key_bytes: int, value_bytes: int) -> float:
    """Bytes of keys and values that must leave their chip in one call:
    ``n * (D - 1) / D * (key_bytes + value_bytes)``."""
    return int(n) * (chips - 1) / chips * (int(key_bytes) + int(value_bytes))


def _itemsize(dtype):
    return 0 if dtype is None else np.dtype(dtype).itemsize


def read(run):
    tr, chips = run.trace, run.cell.chips
    if chips < 2 or tr is None or not tr.calls or not tr.ops:
        return None
    per_call = tr.collective_s_per_call()
    if not per_call:
        return None
    cfg = run.cell.cfg
    need = cross_chip_bytes(int(cfg["n"]), chips, _itemsize(cfg["key_dtype"]),
                            _itemsize(cfg.get("value_dtype")))
    least_s = need / (float(run.peak["ici_bits_per_s"]) / 8 * chips)
    return 100.0 * least_s / per_call
