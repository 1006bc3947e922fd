"""Planted fault for tests/test_rehearsal.py, loaded by the SERVER child
only (the test puts this directory on the child's PYTHONPATH): every
request's first channel is rendered under three quarters of the window
it asked for.  The whole timed path runs; the answer is altered where
it is produced."""

from omero_ms_image_region_tpu.server import settings as _settings

_real = _settings._update_settings


def _altered(rdef, ctx):
    out = _real(rdef, ctx)
    for cb in out.channel_bindings:
        if cb.active:
            cb.input_end = cb.input_start + 0.75 * (
                cb.input_end - cb.input_start)
            break
    return out


_settings._update_settings = _altered
