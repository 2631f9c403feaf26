"""runtime layer (PJRT and libtpu, below coll): the median over the
calls of the first traced window of the runtime's launch, from the
start of PJRT's call into the program (``PJRT_LoadedExecutable_Execute``)
to the end of the last chip's ``TpuLoadedExecutable::ExecuteLaunch``
(host events of the trace, ``Trace.runtime_launch_us``).  Nothing where
the trace holds none of these events, as on the CPU."""


def read(run):
    return run.trace.runtime_launch_us()
