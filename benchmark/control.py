#!/usr/bin/env python3
"""The control of "how `correct` is decided": one run of a cell in which,
after the window, the same sampled requests are also answered by the
plain reference one step BELOW what the configuration states, and judged
by the same comparison.  Each control has to come out as not correct.

    python benchmark/control.py --workload <cell> --seed <n> --seconds <s>

The program's own answers are judged in the same process (its readings
are the limits' lower readings, the controls' the upper ones).  The
benchmark's own runs never run this.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as bench_run                     # noqa: E402

CONTROLS = {
    # Half of each sample's 16 bits and the next IJG quality step down:
    # the two savings (upload bytes, wire bytes) a later PR would be
    # tempted by, together.
    "bits8_q80": {"quality": 80, "data_bits": 8},
    # Each alone, to see which number each moves.
    "bits8": {"quality": 90, "data_bits": 8},
    "q80": {"quality": 80, "data_bits": None},
}


if __name__ == "__main__":
    sys.exit(bench_run.main(controls=CONTROLS))
