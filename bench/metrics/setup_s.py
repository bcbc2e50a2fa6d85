"""Process start to the window's start: imports, device start-up, making
the inputs, and the warm-up call with its compilation (or its reads from
the compile cache), in s."""


def read(run):
    return run.setup_s
