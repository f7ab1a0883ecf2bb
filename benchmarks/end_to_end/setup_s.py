"""Process start to the window's start: signing the traffic, building the
program's objects, the backend's self-check, compiling or loading the
executables, the warm-up requests."""

NAME, UNIT, BETTER, SOURCE = "setup_s", "s", "lower", "host_clock"


def read(ctx):
    return ctx.setup_s
