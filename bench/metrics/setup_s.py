"""Set-up: process start to the opening of the window.  Graph
generation, the program's format build and loading (or compiling)
the cell's executables all fall in it."""
UNIT = "s"


def read(run):
    return run.setup_s
