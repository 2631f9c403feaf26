"""Bus bandwidth over the window: the bus bytes of every call, as the
cell's call module counts them (``bus_bytes``), over the window's wall
time (host clock).  For a collective the bus bytes are the OSU and
nccl-tests model of what crosses each rank's link; for a call that is
no collective, the bytes that cross the rank's links in one call as the
module counts them: for a transfer between HBM and a file, the bytes
that cross the host link.  Which rank counts decide the module's
``bus_bytes``, which gives None where it has no model.  Nothing where
any size has no bus bytes or no size moves any."""


def read(run):
    per_size = [run.call.bus_bytes(s, run.n) for s in run.sizes_bytes]
    if None in per_size or not any(per_size):
        return None
    return sum(per_size[s] for s in run.calls) / run.window_s / 1e9
