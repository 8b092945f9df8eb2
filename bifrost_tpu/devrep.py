"""Device-representation conversion: how each bifrost dtype lives in HBM.

- real/complex float types -> natural jnp dtypes
- ci4/ci8/ci16 -> int8/int8/int16 with a trailing (re, im) axis of
  length 2 — preserves the integer MXU fast path for correlation (the
  Cherk3mEx analogue; reference: src/linalg.cu:130-148)
- a ci8 gulp that goes to ONE device (no ``sharding`` asked for)
  crosses and stays there as int16 words, one a complex sample, in
  the host's own order (:class:`ComplexWords`, below): the same
  array to whoever asks for it, made then
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

A ci8 gulp on one device is words (:class:`ComplexWords`, leaf module
:mod:`bifrost_tpu.words`; docs/transfer.md, "Words").  The runtime
keeps ``s8[16384,2,4096,2]`` as ``{2,0,3,1:T(8,128)(4,1)}``: (re, im)
taken apart and the frames reordered, byte by byte, on the host in
``device_put``; and a reader that wants one int16 a sample (the
spectrometer's kernel) had the device put them back, four passes a
gulp (PERF.md section 6, PR 34).  The words on one axis,
``s16[134217728]``, cross as the host holds them, with no pass of the
host's over them, and a reader's program folds them to the rows it
wants (``s16[32768,4096]``, the kernel's own operand) in one pass of
the device.  ``ReadSpan.data`` still answers the int8 array with its
(re, im) axis (one program, for that reader), ``ReadSpan.words`` the
words; the choice is made from the dtype and from whether a sharding
was asked for, nothing else.  Mesh-scoped gulps, ci4 and ci16 keep the
pairs.
"""

from __future__ import annotations

import numpy as np

from .dtype import DataType
from .planes import ComplexPlanes, device_arrays, whole
from .words import ComplexWords, host_words, words_into
from .xfer import to_device, to_host

__all__ = ['to_device_rep', 'from_device_rep', 'device_rep_zeros',
           'device_rep_dtype', 'ComplexPlanes', 'ComplexWords',
           'device_arrays', 'whole']


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


def _as_words(dtype):
    """ci8: the one dtype whose complex sample is an int16 word."""
    return dtype.kind == 'ci' and dtype.nbits == 8 and dtype.veclen == 1


def to_device_rep(buf, dtype, sharding=None, span=None):
    """numpy storage -> device-representation jax array.  ``sharding``
    (a jax Sharding over the DEVICE-REP shape — note ci* types grow a
    trailing (re, im) axis) places the gulp mesh-resident via the
    sharded H2D path (xfer.to_device).  A ci8 gulp with no sharding
    asked for crosses as its int16 words, the bytes as the host holds
    them, and is a :class:`ComplexWords`.  ``span`` is the open read
    span of a host ring that ``buf`` is the memory of: where the
    device representation is those bytes as they lie (ci8 words, ci16,
    every plain real type), the engine may ship them from the span and
    hold it open instead of copying (``xfer.TransferEngine.to_device``);
    a representation that is computed on the host is a fresh array and
    is staged."""
    dtype = DataType(dtype)
    if _as_words(dtype) and sharding is None:
        from .telemetry import counters
        words = to_device(host_words(buf), span=span)
        counters.inc('xfer.h2d_word_bytes', int(words.nbytes))
        return ComplexWords(words, buf.shape)
    if dtype.kind == 'ci':
        if dtype.nbits == 4:
            b = np.ascontiguousarray(buf).view(np.uint8)
            re = (b.astype(np.int8) >> 4)
            im = (np.left_shift(b, 4).astype(np.int8) >> 4)
            return to_device(np.stack([re, im], axis=-1),
                             sharding=sharding)
        return to_device(np.ascontiguousarray(buf).view(
            buf.dtype[0]).reshape(buf.shape + (2,)), sharding=sharding,
            span=span)
    if dtype.kind == 'cf' and dtype.nbits == 16:
        re = buf['re'].astype(np.float32)
        im = buf['im'].astype(np.float32)
        return to_device(re + 1j * im, sharding=sharding)
    if dtype.is_packed:
        from .ops.map import _to_logical
        return to_device(_to_logical(buf, dtype), sharding=sharding)
    return to_device(buf, sharding=sharding, span=span)


def from_device_rep(arr, dtype, out_buf):
    """device-representation array -> numpy storage (bit-exact inverse)."""
    import jax
    dtype = DataType(dtype)
    if isinstance(arr, ComplexWords):
        # the words are the host's bytes: copy them
        return words_into(to_host(arr.words), out_buf)
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
    """jnp zeros in the device representation of ``dtype``, in the
    form ``to_device_rep`` gives a gulp on one device (ci8: words), so
    that a program compiled for them is the program a gulp runs."""
    import jax.numpy as jnp
    dtype = DataType(dtype)
    if _as_words(dtype):
        return ComplexWords(
            jnp.zeros((int(np.prod(shape, dtype=np.int64)),), jnp.int16),
            shape)
    comp, reim = device_rep_dtype(dtype)
    if reim:
        return jnp.zeros(tuple(shape) + (2,), dtype=comp)
    return jnp.zeros(tuple(shape), dtype=comp)
