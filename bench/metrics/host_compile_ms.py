"""Per call, the host time spent tracing, lowering and compiling programs, or
reading them from the compile cache: the ``compile_ms`` stats of the
program's ``repro.*`` spans inside the call, summed, in ms. Nothing to read
in a trace without the program's spans."""


def read(run):
    tr = run.trace
    per_call = getattr(tr, "program_stat", None)
    if per_call is None or not tr.calls or not tr.program_spans:
        return None
    ms = per_call("compile_ms")
    return sum(ms) / len(ms)
