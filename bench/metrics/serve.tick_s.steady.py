"""Mean of the engine's own ``serve.tick_s`` histogram over the
ticks that ended in the window."""
UNIT = "s"


def read(run):
    return run.record.tick_mean_s
