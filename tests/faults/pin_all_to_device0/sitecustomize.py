"""Planted fault for tests/test_benchmark_fleet_cell.py, loaded by the
SERVER child only: the in-process fleet's partition hands every member
the first device, as a change that lost the pin would.  Every answer
is still right; ``busiest_chip_share`` has to read 100."""

from omero_ms_image_region_tpu.parallel import fleet as _fleet

_real = _fleet.partition_local_devices


def _all_on_the_first(n_members, devices=None):
    return [_real(1, devices)[0] for _ in range(n_members)]


_fleet.partition_local_devices = _all_on_the_first
