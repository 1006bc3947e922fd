"""Planted fault for tests/test_rehearsal.py, loaded by the SERVER child
only: from the moment the window opens (the test's wrapper makes the
file ``BENCH_FAULT_FLAG`` names), every other request is shed with the
server's own 503 and never rendered, as a change that sheds under load
would do it.  The answers that do come are right and come sooner."""

import itertools
import os

from omero_ms_image_region_tpu.server import settings as _settings
from omero_ms_image_region_tpu.server.errors import OverloadedError

_real = _settings._update_settings
_count = itertools.count()


def _shedding(rdef, ctx):
    if os.path.exists(os.environ["BENCH_FAULT_FLAG"]) \
            and next(_count) % 2 == 0:
        raise OverloadedError("planted fault: shed")
    return _real(rdef, ctx)


_settings._update_settings = _shedding
