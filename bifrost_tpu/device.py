"""Device management for the TPU build.

The reference exposes set_device / get_device / stream_synchronize over a
thread-local CUDA stream (reference: src/cuda.cpp:34-99,
python/bifrost/device.py:33-95).  JAX's execution model is different in a
way that *favours* the bifrost pipeline design: every op dispatch is
already asynchronous (the TPU runtime pipelines transfers + compute), so
the per-gulp ``stream_synchronize()`` maps to ``block_until_ready`` on the
arrays produced in that gulp — or to nothing at all, since a downstream
consumer forces the value when it needs it.

Threads select a device with :func:`set_device`; ops read
:func:`get_device` when placing new arrays.
"""

from __future__ import annotations

import threading

_tls = threading.local()


def _devices():
    import jax
    return jax.devices()


_backend_ready = False


def ensure_backend():
    """Initialize the jax backend from the CALLING thread (idempotent).

    Pipeline.run() calls this from the launching thread before it
    spawns block threads, so a backend that cannot start — no chip, or
    a chip another process holds (a chip belongs to one process at a
    time) — raises out of run() on the caller's thread with its own
    traceback, not inside a block thread under the supervisor.
    """
    global _backend_ready
    if _backend_ready:
        return
    import jax
    jax.devices()
    _backend_ready = True


def set_device(device):
    """Bind this thread to a device (reference: bfDeviceSet, src/cuda.cpp).
    Accepts an int index or a jax Device."""
    if device is None:
        _tls.device = None
        return
    if isinstance(device, int):
        device = _devices()[device]
    _tls.device = device


def get_device():
    """The jax Device bound to this thread (default device if unset)."""
    dev = getattr(_tls, 'device', None)
    if dev is None:
        dev = _devices()[0]
    return dev


def get_bound_device():
    """The explicitly bound device for this thread, or None — lets
    transfer paths honor BlockScope(device=N) without forcing a
    placement when none was requested."""
    return getattr(_tls, 'device', None)


def get_device_index():
    return get_device().id


def stream_synchronize(*arrays):
    """Wait for async work. With arguments, blocks until those arrays are
    ready; with no arguments this is a no-op by design — JAX data
    dependencies give the ordering the reference got from
    cudaStreamSynchronize (reference: pipeline.py:628)."""
    import jax
    for a in arrays:
        if hasattr(a, 'as_jax') and a.space == 'tpu':
            a = a.data
        if isinstance(a, jax.Array) and not a.is_deleted():
            # deleted arrays were donated downstream (xfer buffer
            # donation): their computation was consumed — nothing left
            # to wait on
            a.block_until_ready()


def force_completion(*arrays):
    """Force device execution of ``arrays`` to COMPLETE via a one-element
    value readback — the strict-mode wait (BF_SYNC_STRICT=1).

    On the local v5e ``block_until_ready`` does wait for the device: on
    a one-second program it returned 2 ms before a scalar read-back of
    the result did (chip_smoke.py fact i, PR 21), so this is no longer
    needed for correctness there; removing it and strict mode is
    ROADMAP D6's.  Because the TPU runtime executes in enqueue order,
    forcing the newest array implies everything enqueued before it has
    finished.  Complex arrays read back their real part."""
    import jax
    import jax.numpy as jnp
    for a in arrays:
        if hasattr(a, 'as_jax') and getattr(a, 'space', None) == 'tpu':
            a = a.data
        if isinstance(a, jax.Array) and a.size and not a.is_deleted():
            # donated (deleted) arrays are skipped — see
            # stream_synchronize
            x = jnp.ravel(a)[0]
            if jnp.issubdtype(a.dtype, jnp.complexfloating):
                x = jnp.real(x)
            float(x)


def execution_in_order():
    """Whether the backend executes dispatched work in enqueue order —
    the assumption that lets the pipeline's dispatch-ahead drain wait on
    only the newest gulp.  All supported backends (TPU single-stream
    runtime, CPU) are in-order; set BF_ASSUME_IN_ORDER=0 to make drains
    wait on every outstanding gulp instead."""
    import os
    return os.environ.get('BF_ASSUME_IN_ORDER', '1') != '0'


class ExternalStream(object):
    """No-op context manager kept for API compatibility with the
    reference's cupy/pycuda interop (reference: device.py:56-84)."""

    def __init__(self, stream=None):
        self.stream = stream

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False
