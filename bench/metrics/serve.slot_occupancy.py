"""Active slot-ticks over all slot-ticks in the window: the engine's
``serve.slot_occupancy`` gauge sampled after each tick."""
UNIT = "%"


def read(run):
    samples = run.record.occupancy
    return 100.0 * sum(samples) / len(samples) if samples else None
