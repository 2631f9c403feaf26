"""Bind a remote rank to its chip on the host it runs on, then exec it.

    python -m ompi_tpu.boot.bind <command> [args...]

The plm/rsh leg prefixes each remote rank's command with this: the
launcher's own chips (a CPU head node may have none) say nothing about
the remote host's, so the rank's slot (:data:`ENV_HOST_SLOT`) rides the
environment and the chip count, binding and SliceBuilder port are
worked out here, where libtpu will load.
"""

from __future__ import annotations

import os
import sys

from .proc import ENV_HOST_SLOT
from .tpurun import tpu_binding, tpu_chip_count


def main(argv: list[str]) -> None:
    local_rank, local_np = map(int, os.environ.pop(ENV_HOST_SLOT).split(","))
    os.environ.update(tpu_binding(local_rank, local_np, tpu_chip_count()))
    os.execvp(argv[0], argv)


if __name__ == "__main__":
    main(sys.argv[1:])
