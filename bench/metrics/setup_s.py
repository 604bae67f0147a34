"""setup_s: process start to the first timed operation, compiling or
loading every program the window uses included (host clock)."""


def read(run):
    return run["setup_s"]
