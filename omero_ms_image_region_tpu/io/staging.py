"""Host->device staging of raw pixel planes.

A raw plane that missed the HBM raw cache goes up as it is: one
asynchronous ``jax.device_put`` of the storage-dtype array, at the call
site, and no program runs on the device for an upload.  The link is a
local bus that carries a 24 MB plane in milliseconds while the chip
runs the previous group; a transform sized to a slower link costs the
chip more than it spares the bus (a bit-packed upload's device unpack
measured 200 ms a plane, twice the JPEG program: PERF.md section 6).
This module holds what the upload's callers share: the per-member
device pin and the content-digest skip.

Reference context: the reference's Bio-Formats path materializes raw
planes host-side and hands byte[] buffers to the renderer in-process
(``ImageRegionRequestHandler.java:302-309,559``); it never pays a
device link, so this stage has no Java counterpart.
"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def pin_scope(device):
    """Run the enclosed dispatches on ``device`` — per-member device
    pinning for the combined federated role (``parallel.federation``
    partitions ``jax.local_devices()`` across a host's members, so
    each member's staging and render executes on ITS device set).
    ``None`` yields straight through: the process default device, the
    pre-federation behavior, at zero cost."""
    if device is None:
        yield
        return
    import jax
    with jax.default_device(device):
        yield


def stage_deduped(arr: np.ndarray, cache, digest: str = None):
    """Digest-first staging: skip the upload when the content is already
    device-resident.

    ``cache`` is an ``io.devicecache.DeviceRawCache`` with its digest
    index on.  Returns ``(device_array, digest, was_resident)``:
    ``was_resident`` True means zero bytes crossed the host->device link
    (the plane was found under some key — a prior wire push, or the same
    content staged for another region identity).  On a miss the plane
    is uploaded (``DeviceRawCache.get_or_load``) and recorded under
    its content key, so the NEXT identical push — from any
    frontend, for any region identity — skips the wire.

    This is the server half of the sidecar's digest-first plane
    protocol (``server.sidecar``: ``plane_probe`` then ``plane_put``
    only on miss), and the in-process staging skip for everything else.
    """
    from .devicecache import plane_digest, plane_key

    digest = digest or plane_digest(arr)
    resident = cache.get_by_digest(digest)
    if resident is not None:
        cache.count_plane(hit=True)
        from ..utils import telemetry
        telemetry.add_cost("staged_bytes_skipped", arr.nbytes)
        return resident, digest, True
    staged = cache.get_or_load(plane_key(digest), lambda: arr,
                               digest=digest)
    return staged, digest, False
