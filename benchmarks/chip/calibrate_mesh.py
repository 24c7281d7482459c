#!/usr/bin/env python3
"""``calibrate.py`` with the faults of the data-parallel driver
(``drivers/train_stream_dp4.py`` ``FAULTS``) among the names ``--fault``
takes, on the chip:

    python3 benchmarks/chip/calibrate_mesh.py --workload 100m-train-dp4 \
        --seeds s1 --control-seeds s2 --fault quarter_batch --seconds 2

The seeds run with the fault planted; the control seeds read the
control (the reference in bfloat16 in the program's place).
"""
from __future__ import annotations

import sys

import calibrate


def main(argv=None) -> int:
    from chip import faults as F
    from chip.drivers import train_stream_dp4 as D
    F.HOOKS.update(D.FAULTS)
    return calibrate.main(argv)


if __name__ == "__main__":
    sys.exit(main())
