"""Host-side synchronization helpers.

A per-value ``float(device_array)`` waits for the device and copies one
scalar to the host PER CALL, stalling the dispatch queue each time — so
training loops keep losses on device and fetch them in one batched transfer
at the end. (On the local chip ``jax.block_until_ready`` is a real barrier
and a dispatch costs microseconds; the batching is about not draining the
queue once per step.)
"""

from __future__ import annotations

import jax


def fetch_losses(losses):
    """One batched host fetch of a list of device scalars -> list[float].

    ``jax.device_get`` on the whole list starts every transfer
    asynchronously before awaiting any of them — a single effective
    round-trip, vs one per element for per-item ``float()``.
    """
    if not losses:
        return []
    return [float(v) for v in jax.device_get(losses)]
