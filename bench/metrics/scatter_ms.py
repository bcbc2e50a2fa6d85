"""Per call, the device-busy time of the programs launched inside the
program's ``repro.stage.scatter`` spans, the final reorder, mean over chips,
in ms. Nothing to read in a trace that holds no launch in those spans."""

STAGES = ("repro.stage.scatter",)


def read(run):
    tr = run.trace
    per_call = getattr(tr, "stage_device_s", None)
    if per_call is None or not tr.calls:
        return None
    secs = per_call(STAGES)
    return sum(secs) / len(secs) * 1e3 if any(secs) else None
