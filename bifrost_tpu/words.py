"""The chunk type of a device ring that holds a ci8 gulp as the host
holds it: one 16-bit word a complex sample (little-endian: low byte
re, high byte im), all of them on one axis, in the host's order.  A
leaf module, as :mod:`bifrost_tpu.planes` is (it imports nothing of
the package), so that the ring, the dispatch-ahead queue, the transfer
engine and :mod:`bifrost_tpu.devrep` can all name the type.

Why words: the TPU runtime keeps an int8 array with a trailing
(re, im) axis of two with that axis far from minor-most
(``s8[16384,2,4096,2]{2,0,3,1:T(8,128)(4,1)}``), so ``device_put``
transposes every gulp byte-wise on the host, and a kernel that wants
one int16 a sample (ops/spectrometer.py) has the device put the bytes
back as the host had them, four passes a gulp.  Why one axis: an
int16 array of two or more axes is still laid out in tiles by the
runtime's threads on the host (rows of 4096 as ``T(8,128)(2,1)``), and
beside the benchmark's source that work costs the served cells more
than the byte-wise transposition did; one axis crosses as it lies,
with no pass of the host's at all, and a reader's program folds it to
the rows it wants in one pass of the device (PERF.md section 6, PR 34;
docs/transfer.md, "Words").
"""

from __future__ import annotations

import numpy as np

__all__ = ['ComplexWords', 'host_view', 'host_words', 'words_into',
           'pairs_of']

_pairs_fn = None


def host_view(buf):
    """Host ci8 storage (two bytes a sample: a structured (re, im)
    int8 pair) seen as its int16 words on one axis, or None where
    ``buf`` is not one stretch of bytes."""
    if not buf.flags.c_contiguous or buf.dtype.itemsize != 2:
        return None
    return buf.view(np.int16).reshape(-1)


def host_words(buf):
    """The int16 words of host ci8 storage: a view where ``buf`` is
    contiguous, a copy otherwise."""
    return host_view(np.ascontiguousarray(buf))


def words_into(host, out):
    """Host int16 words into ci8 storage ``out``: the words are the
    storage's bytes, so this is one copy, field by field only where
    ``out`` is not one stretch of bytes."""
    view = host_view(out)
    if view is not None:
        view[...] = host
    else:
        pairs = host.view(np.int8).reshape(out.shape + (2,))
        out['re'] = pairs[..., 0]
        out['im'] = pairs[..., 1]
    return out


def pairs_of(words, shape):
    """Words as the int8 array of ``shape`` (its last axis the
    (re, im) pair), under jit or outside: a bitcast and a reshape,
    which the TPU's compiler turns into passes over the gulp where a
    reader wants the pairs whole (PERF.md section 6, PR 34)."""
    import jax.numpy as jnp
    from jax import lax
    return lax.bitcast_convert_type(words, jnp.int8).reshape(shape)


class ComplexWords(object):
    """One ci8 array on the device as its int16 ``words``, one axis of
    as many as the array has samples: what a device ring span holds
    where ``devrep.to_device_rep`` put a ci8 gulp on one device.  It
    answers what the ring, the dispatch-ahead queue and the transfer
    engine ask of a chunk (shape, dtype, bytes, placement, readiness,
    deletion, a slice) as the int8 array with its trailing (re, im)
    axis would; that array exists only once somebody asks for it
    (:meth:`pairs`, one program)."""

    __slots__ = ('words', '_shape')

    def __init__(self, words, shape):
        shape = tuple(int(s) for s in shape)
        size = int(np.prod(shape, dtype=np.int64))
        if words.dtype != np.int16 or tuple(words.shape) != (size,):
            raise ValueError(
                "words of a ci8 array of shape %s are int16(%d,) (got "
                "%s%s)" % (shape, size, words.dtype, tuple(words.shape)))
        self.words, self._shape = words, shape

    @property
    def shape(self):
        """Of the int8 array the words stand for: (..., 2)."""
        return self._shape + (2,)

    @property
    def ndim(self):
        return len(self._shape) + 1

    @property
    def dtype(self):
        return np.dtype(np.int8)

    @property
    def nbytes(self):
        return int(self.words.nbytes)

    @property
    def sharding(self):
        return self.words.sharding

    def is_ready(self):
        return self.words.is_ready()

    def is_deleted(self):
        return self.words.is_deleted()

    def block_until_ready(self):
        self.words.block_until_ready()
        return self

    def __getitem__(self, idx):
        """A stretch of the first axis longer than one (a ring's
        frames) stays words, a stretch of them; anything else is taken
        from the pairs."""
        idx = idx if isinstance(idx, tuple) else (idx,)
        axis = next((i for i, n in enumerate(self._shape) if n != 1),
                    0)
        if axis < len(idx) <= self.ndim and self._shape and \
                all(isinstance(i, slice) for i in idx) and \
                all(i == slice(None) for k, i in enumerate(idx)
                    if k != axis):
            start, stop, step = idx[axis].indices(self._shape[axis])
            if step == 1:
                per = int(np.prod(self._shape[axis + 1:], dtype=np.int64))
                stop = max(stop, start)
                shape = list(self._shape)
                shape[axis] = stop - start
                return ComplexWords(
                    self.words[start * per:stop * per], shape)
        return self.pairs()[idx]

    def __array__(self, dtype=None, copy=None):
        host = np.asarray(self.words).view(np.int8).reshape(self.shape)
        return host if dtype is None else host.astype(dtype)

    def pairs(self):
        """The int8 array (..., 2), made now: one program."""
        global _pairs_fn
        if _pairs_fn is None:
            import jax
            _pairs_fn = jax.jit(pairs_of, static_argnums=1)
        return _pairs_fn(self.words, self.shape)
