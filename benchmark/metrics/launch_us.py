"""coll layer: the median over the calls of the second traced window
of ``ompi.coll.launch``, the call of the compiled program, which starts
it on every chip (library spans, ``libspans.launch_us``).  Nothing for
a call module that names no api span."""

from benchmark import libspans


def read(run):
    args = libspans.of_run(run)
    return None if args is None else libspans.launch_us(*args[:2])
