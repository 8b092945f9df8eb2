"""The correlator CORNER TURN as an on-chip collective.

An FX correlator's F-stage is time-major (each engine channelizes its
own time slice) while the X-stage is channel-major (each engine wants
EVERY station's voltages for its channels, over the whole integration).
The redistribution between them — time/station-major to channel-major —
is the classic corner turn, the bandwidth bottleneck of every large
correlator (reference: Bifrost moves it over UDP between servers,
python/bifrost/packet_writer.py; CHIME and LEDA burn whole switch
fabrics on it).

On a TPU mesh the corner turn never leaves the package: the gulp is
time-sharded (T/D, F, ...) per device and must become channel-sharded
(T, F/D, ...).  Two interchangeable primitives:

- ``impl='xla'`` — one ``jax.lax.all_to_all`` (split the channel axis,
  concatenate the time axis), lowered by XLA to the ICI all-to-all.
- ``impl='pallas'`` / ``impl='ring'`` — D-1 neighbour hops around the
  mesh ring; each hop rotates the full block one device to the right
  (Pallas ``make_async_remote_copy`` kernel on TPU, a ``ppermute`` in
  the 'ring' reference form) and each device peels off the channel
  chunk addressed to it.  Same math, explicit ring schedule — raced
  against the XLA form under ops.mprobe (family ``corner_turn``, see
  blocks.correlate) rather than assumed faster.

Both forms are pure redistributions: byte-identical outputs, equal to
the global transpose oracle ``x.reshape(D, T/D, ...)`` per-shard
restitch (tests/test_correlate.py proves it on a CPU mesh).
"""

from __future__ import annotations

__all__ = ['corner_turn_local', 'corner_turn']

from .ops import _P


def _ppermute_shift(x, axis_name, ndev):
    """Reference ring hop: device i's block lands on (i+1) % D."""
    import jax
    perm = [(i, (i + 1) % ndev) for i in range(ndev)]
    return jax.lax.ppermute(x, axis_name, perm)


def _pallas_shift(x, axis_name, ndev):
    """Ring hop as an explicit remote DMA (ops.pallas_kernels)."""
    from ..ops.pallas_kernels import ring_permute
    return ring_permute(x, axis_name, ndev)


def _ring_corner_turn(x, axis_name, ndev, shift):
    """Corner turn composed from D-1 ring hops: after hop k this
    device holds the block of device (i-k); it peels off channel chunk
    #i — the chunk that source addressed to it — and finally orders
    the chunks by SOURCE device so the stacked result equals the
    all_to_all/transpose oracle."""
    import jax.numpy as jnp
    from jax import lax
    idx = lax.axis_index(axis_name)
    t_loc, f = x.shape[0], x.shape[1]
    fc = f // ndev

    def my_chunk(buf):
        return lax.dynamic_slice_in_dim(buf, idx * fc, fc, axis=1)

    parts = [my_chunk(x)]
    buf = x
    for _ in range(ndev - 1):
        buf = shift(buf, axis_name, ndev)
        parts.append(my_chunk(buf))
    # parts[k] came from device (idx - k) mod D; reorder so slot s
    # holds source s's chunk, then flatten to the global time order
    stacked = jnp.stack(parts)                        # (D, T/D, F/D, ..)
    order = jnp.mod(idx - jnp.arange(ndev), ndev)
    ordered = jnp.take(stacked, order, axis=0)
    return ordered.reshape((ndev * t_loc, fc) + x.shape[2:])


def corner_turn_local(x, axis_name, impl='xla', ndev=None):
    """Per-shard corner turn (call inside shard_map over
    ``axis_name``): local block (T/D, F, ...) -> (T, F/D, ...), i.e.
    the gulp goes from time-sharded to channel-sharded.  Requires
    D | F.  ``impl``: 'xla' (lax.all_to_all), 'pallas' (remote-DMA
    ring kernel, TPU only), 'ring' (ppermute reference ring)."""
    from jax import lax
    if impl in ('pallas', 'ring'):
        if ndev is None:
            ndev = lax.axis_size(axis_name)
        if not isinstance(ndev, int):
            raise ValueError('ring corner turn needs a static device '
                             'count; pass ndev=')
        shift = _pallas_shift if impl == 'pallas' else _ppermute_shift
        return _ring_corner_turn(x, axis_name, ndev, shift)
    if impl != 'xla':
        raise ValueError("corner turn impl %r not in "
                         "('xla', 'pallas', 'ring')" % (impl,))
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=0,
                          tiled=True)


def corner_turn(mesh, axis_name, impl='xla', stacked=False):
    """Host-level wrapper for tests/tools: returns fn(x) over a GLOBAL
    (T, F, ...) array, shard_map'd so the input commits time-sharded
    and the output channel-sharded.  Globally the corner turn is an
    identity (it only moves shards), so ``stacked=True`` instead
    returns (D, T, F/D, ...) with slot d = device d's post-turn shard,
    comparable against the transpose oracle
    ``x[:, d*F/D:(d+1)*F/D]``."""
    from jax import shard_map
    ndev = int(mesh.shape[axis_name])

    def call(x):
        in_spec = _P(*([axis_name] + [None] * (x.ndim - 1)))
        if stacked:
            out_spec = _P(*([axis_name] + [None] * x.ndim))
            fn = shard_map(
                lambda b: corner_turn_local(b, axis_name, impl=impl,
                                            ndev=ndev)[None],
                mesh=mesh, in_specs=in_spec, out_specs=out_spec)
        else:
            out_spec = _P(*([None, axis_name] +
                            [None] * (x.ndim - 2)))
            fn = shard_map(
                lambda b: corner_turn_local(b, axis_name, impl=impl,
                                            ndev=ndev),
                mesh=mesh, in_specs=in_spec, out_specs=out_spec)
        return fn(x)
    return call
