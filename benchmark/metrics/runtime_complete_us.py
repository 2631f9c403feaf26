"""runtime layer (PJRT and libtpu, below coll): the median over the
calls of the first traced window of the runtime's completion, from the
start of the first chip's ``ReadSyncFlag`` after the call's launches
have ended to the end of the last chip's ``CompleteCallbacks`` (host
events of the trace, ``Trace.runtime_complete_us``).  What follows, up
to the caller seeing the result, is outside the runtime and not
counted.  Nothing where the trace holds none of these events, as on
the CPU."""


def read(run):
    return run.trace.runtime_complete_us()
