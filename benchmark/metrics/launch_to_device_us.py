"""coll layer: the median over the calls of the second traced window
of the time from the start of ``ompi.coll.launch`` to the start of the
call's operation on the chips, averaged over the chips (library spans
and device trace, ``libspans.launch_to_device_us``).

It compares the host's clock with the chips', and the trace bounds
their offset only from causality (``libspans.clock_offset_us``).  So it
is reported only where those bounds hold 0 within the number's own
precision: they contain 0, and neither lies farther from 0 than
``PRECISION`` of the number.  Elsewhere the unknown offset could move it
by more than that, and nothing is reported."""

from benchmark import libspans

#: the share of the number by which the clocks' offset may be unknown
PRECISION = 0.01


def read(run):
    args = libspans.of_run(run)
    if args is None:
        return None
    tr, lib, _ = args
    value = libspans.launch_to_device_us(tr, lib)
    bounds = libspans.clock_offset_us(tr, lib)
    if value is None or bounds is None:
        return None
    lo, hi = bounds
    if not lo <= 0 <= hi or max(-lo, hi) > PRECISION * value:
        return None
    return value
