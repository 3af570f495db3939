"""Pluggable NVM cache-emulation backends.

``MemoryBackend`` (base.py) is the narrow protocol; three
implementations ship here:

* ``reference`` — :class:`ReferenceLRUBackend`, exact per-entry
  OrderedDict semantics; the oracle.
* ``vectorized`` — :class:`VectorizedBackend`, batched bitmap/stamp
  arrays; the default, byte-equivalent to the oracle and ~10-100x
  faster on range traffic.
* ``device`` — :class:`DeviceBackend`, the vectorized backend with
  large eviction-free span ops and queue-validity scans lifted onto
  jax-jit kernels; byte-equivalent to both, falls back to the
  vectorized host path below a size floor or under eviction pressure.

Select with ``NVMConfig(backend="...")`` or the ``REPRO_NVM_BACKEND``
environment variable. See README.md in this directory.
"""

from __future__ import annotations

from .base import (LineSurvival, MediaFault, MemoryBackend,
                   corrupt_image_words, select_survivors)
from .device import DeviceBackend
from .reference import ReferenceLRUBackend
from .vectorized import VectorizedBackend

__all__ = ["MemoryBackend", "LineSurvival", "select_survivors",
           "MediaFault", "corrupt_image_words",
           "ReferenceLRUBackend", "VectorizedBackend", "DeviceBackend",
           "BACKENDS", "make_backend"]

BACKENDS = {
    ReferenceLRUBackend.kind: ReferenceLRUBackend,
    VectorizedBackend.kind: VectorizedBackend,
    DeviceBackend.kind: DeviceBackend,
}


def make_backend(kind: str, store, cfg) -> MemoryBackend:
    try:
        cls = BACKENDS[kind]
    except KeyError:
        raise ValueError(
            f"unknown NVM backend {kind!r}; available: {sorted(BACKENDS)}"
        ) from None
    return cls(store, cfg)
