"""Bus bandwidth over the window: the bus bytes of every call, as the
cell's call module counts them (``bus_bytes``, the OSU and nccl-tests
model of what crosses each rank's link), over the window's wall time
(host clock).  Nothing where the module has no bus model or one rank
moves nothing."""


def read(run):
    per_size = [run.call.bus_bytes(s, run.n) for s in run.sizes_bytes]
    if run.n < 2 or None in per_size:
        return None
    return sum(per_size[s] for s in run.calls) / run.window_s / 1e9
