"""Per call, the device-busy time of the programs launched inside the
program's tile-stage spans (``repro.stage.prescan``, ``.scan``,
``.postscan``), mean over chips, in ms. Nothing to read in a trace that holds
no launch in those spans: a program without them, or a reduction that does
not tie launches to spans."""

STAGES = ("repro.stage.prescan", "repro.stage.scan", "repro.stage.postscan")


def read(run):
    tr = run.trace
    per_call = getattr(tr, "stage_device_s", None)
    if per_call is None or not tr.calls:
        return None
    secs = per_call(STAGES)
    return sum(secs) / len(secs) * 1e3 if any(secs) else None
