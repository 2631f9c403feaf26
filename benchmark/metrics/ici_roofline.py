"""device layer, the all-reduce itself: the least time the chips could
take for every traced call (``arith.allreduce_floor_s``: the bytes each
chip sends over its ICI rate, or 2S over HBM bandwidth, whichever is
larger) over the device time of the all-reduce operations, averaged
over the chips, in percent (trace)."""

from benchmark import arith, trace


def read(run):
    spent = run.trace.op_seconds(
        lambda op: trace.opcode(op).startswith("all-reduce"))
    if spent <= 0:
        return None
    pk = arith.peaks(run.device_kind)
    floor = sum(arith.allreduce_floor_s(run.sizes_bytes[s], run.n, pk)[0]
                for s in run.calls)
    return floor / spent * 100.0
