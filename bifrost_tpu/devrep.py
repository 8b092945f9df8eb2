"""Device-representation conversion: how each bifrost dtype lives in HBM.

- real/complex float types -> natural jnp dtypes
- ci4/ci8/ci16 -> int8/int8/int16 with a trailing (re, im) axis of
  length 2 — preserves the integer MXU fast path for correlation (the
  Cherk3mEx analogue; reference: src/linalg.cu:130-148)
- packed sub-byte ints -> unpacked int8
- cf16 -> complex64

Conversions are bit-exact round trips.  All transfers ride
:mod:`bifrost_tpu.xfer`: a complex array goes to the device as (re, im)
float planes, comes back whole as the complex64 it is, and comes back
in pieces (a large product bound for a ring span) as rows of 32-bit
words with re and im interleaved, which the host sees as complex with
a view; ``from_device_rep`` is handed complex either way.

A complex array that a block computed on two real planes may stay
them in a device ring: :class:`ComplexPlanes` (defined in the leaf
module :mod:`bifrost_tpu.planes`, which the ring and the transfer
engine import, and named here too) is one chunk of a span,
complex64 to whoever asks for the span's array (joined then, for that
reader) and two float32 arrays to a reader that can use planes, which
is how a correlator's product reaches the D2H cut with no complex64
program on the way (docs/transfer.md, "Planes").
"""

from __future__ import annotations

import numpy as np

from .dtype import DataType
from .planes import ComplexPlanes, device_arrays
from .xfer import to_device, to_host

__all__ = ['to_device_rep', 'from_device_rep', 'device_rep_zeros',
           'device_rep_dtype', 'ComplexPlanes', 'device_arrays']


def device_rep_dtype(dtype):
    """(jnp dtype, has_reim_axis) for a bifrost dtype's device form."""
    import jax.numpy as jnp
    dtype = DataType(dtype)
    if dtype.kind == 'ci':
        comp = jnp.int8 if dtype.nbits <= 8 else (
            jnp.int16 if dtype.nbits == 16 else jnp.int32)
        return comp, True
    if dtype.kind == 'cf' and dtype.nbits == 16:
        return jnp.complex64, False
    if dtype.is_packed:
        return (jnp.int8 if dtype.kind == 'i' else jnp.uint8), False
    return jnp.dtype(dtype.as_jax_dtype()), False


def to_device_rep(buf, dtype, sharding=None):
    """numpy storage -> device-representation jax array.  ``sharding``
    (a jax Sharding over the DEVICE-REP shape — note ci* types grow a
    trailing (re, im) axis) places the gulp mesh-resident via the
    sharded H2D path (xfer.to_device)."""
    dtype = DataType(dtype)
    if dtype.kind == 'ci':
        if dtype.nbits == 4:
            b = np.ascontiguousarray(buf).view(np.uint8)
            re = (b.astype(np.int8) >> 4)
            im = (np.left_shift(b, 4).astype(np.int8) >> 4)
            return to_device(np.stack([re, im], axis=-1),
                             sharding=sharding)
        return to_device(np.ascontiguousarray(buf).view(
            buf.dtype[0]).reshape(buf.shape + (2,)), sharding=sharding)
    if dtype.kind == 'cf' and dtype.nbits == 16:
        re = buf['re'].astype(np.float32)
        im = buf['im'].astype(np.float32)
        return to_device(re + 1j * im, sharding=sharding)
    if dtype.is_packed:
        from .ops.map import _to_logical
        return to_device(_to_logical(buf, dtype), sharding=sharding)
    return to_device(buf, sharding=sharding)


def from_device_rep(arr, dtype, out_buf):
    """device-representation array -> numpy storage (bit-exact inverse)."""
    import jax
    dtype = DataType(dtype)
    if isinstance(arr, (jax.Array, ComplexPlanes)):
        arr = to_host(arr)
    else:
        arr = np.asarray(arr)
    if dtype.kind == 'ci':
        if dtype.nbits == 4:
            re = arr[..., 0].astype(np.int64) & 0xF
            im = arr[..., 1].astype(np.int64) & 0xF
            packed = ((re << 4) | im).astype(np.uint8)
            out_buf[...] = packed.reshape(out_buf.shape) \
                if out_buf.dtype == np.uint8 \
                else packed.view(out_buf.dtype).reshape(out_buf.shape)
            return out_buf
        out_buf['re'] = arr[..., 0]
        out_buf['im'] = arr[..., 1]
        return out_buf
    if dtype.kind == 'cf' and dtype.nbits == 16:
        out_buf['re'] = arr.real
        out_buf['im'] = arr.imag
        return out_buf
    if dtype.is_packed:
        from .ops.quantize import _pack_into
        _pack_into(arr, dtype, out_buf)
        return out_buf
    out_buf[...] = arr.reshape(out_buf.shape)
    return out_buf


def device_rep_zeros(shape, dtype):
    """jnp zeros in the device representation of ``dtype``."""
    import jax.numpy as jnp
    comp, reim = device_rep_dtype(dtype)
    if reim:
        return jnp.zeros(tuple(shape) + (2,), dtype=comp)
    return jnp.zeros(tuple(shape), dtype=comp)
