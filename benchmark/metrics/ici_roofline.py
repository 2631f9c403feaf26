"""device layer, the collective itself: the least time the chips could
take for every traced call (the call module's ``floor_s``, from the
chip's peaks over the ICI links that the slice wires to each chip,
``run.ici_links``) over the device time of its operations (opcodes
that start with its ``DEVICE_OPS``), averaged over the chips, in
percent (trace).  Nothing where the module has no floor or the chips
have no coords to count links by."""

from benchmark import arith, trace


def read(run):
    spent = run.trace.op_seconds(
        lambda op: trace.opcode(op).startswith(run.call.DEVICE_OPS))
    if spent <= 0 or run.ici_links is None:
        return None
    pk = arith.peaks(run.device_kind, run.ici_links)
    per_size = [run.call.floor_s(s, run.n, pk) for s in run.sizes_bytes]
    if None in per_size:
        return None
    return sum(per_size[s] for s in run.calls) / spent * 100.0
