"""api layer: the median over the calls of the second traced window of
the call module's api span (``API_SPAN``) less the time its coll
children cover: the library's own Python per call (library spans,
``libspans.api_self_us``)."""

from benchmark import libspans


def read(run):
    args = libspans.of_run(run)
    return None if args is None else libspans.api_self_us(*args)
