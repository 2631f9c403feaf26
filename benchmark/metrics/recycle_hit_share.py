"""mesh/arena layer: the share of the call module's api spans in the
second traced window whose ``recycled`` arg is 1, the calls whose
result went into a dropped earlier one from the communicator's spare
pool, in percent (library spans, ``libspans.recycle_hit_share``)."""

from benchmark import libspans


def read(run):
    args = libspans.of_run(run)
    return None if args is None else libspans.recycle_hit_share(*args)
