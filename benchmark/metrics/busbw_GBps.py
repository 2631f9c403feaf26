"""Bus bandwidth over the window: the OSU bus bytes of every call,
2(n-1)/n of its message, over the window's wall time (host clock)."""

from benchmark import arith


def read(run):
    if run.n < 2:
        return None
    moved = sum(arith.bus_bytes(run.sizes_bytes[s], run.n) for s in run.calls)
    return moved / run.window_s / 1e9
