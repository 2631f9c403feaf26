"""device layer: the share of the traced window in which the chips ran
no operation and no copy, averaged over the chips (trace)."""


def read(run):
    idle = run.trace.idle_share()
    return None if idle is None else idle * 100.0
