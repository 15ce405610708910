"""setup_s: host seconds from the first call into the program, once the
inputs exist, to the opening of the window: plan, upload, compile or cache
load, and the warm-up of every shape the window uses."""


def read(run):
    return run.setup_s
