"""The chunk type of a device ring that is not one jax array: a complex
array as the two real planes it was computed in.  A leaf module (it
imports nothing of the package but its fellow leaf
:mod:`bifrost_tpu.words`, the other such chunk type), so that the
ring, the dispatch-ahead queue, the transfer engine and
:mod:`bifrost_tpu.devrep` can all name the type without depending on
one another.
"""

from __future__ import annotations

import numpy as np

from .words import ComplexWords

__all__ = ['ComplexPlanes', 'device_arrays', 'whole']

_join_fn = None


class ComplexPlanes(object):
    """One complex array on the device as the two same-shaped real
    arrays it was computed in, ``re`` and ``im``: what a device ring
    span holds where its writer set one (``WriteSpan.set``).  It
    answers what the ring, the dispatch-ahead queue and the transfer
    engine ask of a chunk (shape, bytes, placement, readiness,
    deletion, a slice) as the complex array would; the complex array
    itself exists only once somebody asks for it (:meth:`joined`).
    The TPU computes complex64 on separate planes anyway, and a
    program with a complex argument splits all of it before it does
    anything else (PERF.md section 6, PR 31)."""

    __slots__ = ('re', 'im')

    def __init__(self, re, im):
        if re.shape != im.shape or re.dtype != im.dtype or \
                re.dtype.kind != 'f':
            raise ValueError(
                "planes must be two float arrays of one shape and "
                "dtype (got %s%s and %s%s)"
                % (re.dtype, re.shape, im.dtype, im.shape))
        self.re, self.im = re, im

    @property
    def shape(self):
        return self.re.shape

    @property
    def ndim(self):
        return self.re.ndim

    @property
    def dtype(self):
        """Of the array the planes stand for: complex64 for float32."""
        return np.result_type(self.re.dtype, np.complex64)

    @property
    def nbytes(self):
        return 2 * int(self.re.nbytes)

    @property
    def sharding(self):
        return self.re.sharding

    def is_ready(self):
        return self.re.is_ready() and self.im.is_ready()

    def is_deleted(self):
        return self.re.is_deleted() or self.im.is_deleted()

    def __getitem__(self, idx):
        return ComplexPlanes(self.re[idx], self.im[idx])

    def joined(self):
        """The complex array, made now: one program, moves alone."""
        global _join_fn
        if _join_fn is None:
            import jax
            from jax import lax
            _join_fn = jax.jit(lax.complex)
        return _join_fn(self.re, self.im)


def device_arrays(chunk):
    """The jax arrays a device ring chunk is made of."""
    if isinstance(chunk, ComplexPlanes):
        return (chunk.re, chunk.im)
    if isinstance(chunk, ComplexWords):
        return (chunk.words,)
    return (chunk,)


def whole(chunk):
    """A device ring chunk as the one array a reader of ``.data``
    sees: planes joined, words as (re, im) pairs, made now for that
    reader; an array as it is."""
    if isinstance(chunk, ComplexPlanes):
        return chunk.joined()
    if isinstance(chunk, ComplexWords):
        return chunk.pairs()
    return chunk
