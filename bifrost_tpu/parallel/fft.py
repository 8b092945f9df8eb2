"""Distributed FFT over a sharded transform axis (pencil / four-step
decomposition with all-to-all transposes).

This is the long-sequence answer the reference has no analogue for —
its FFT is bounded by one GPU's memory (reference: src/fft.cu plans are
single-device; multi-GPU runs split WHOLE transforms across streams,
never one transform across devices).  Here one FFT of length
N = N1 * N2 runs across the D devices of a mesh axis:

    x[n], n = N2*p + q, contiguous n chunks per device (p sharded)
    1. all_to_all: redistribute so q is sharded, p local
    2. local DFT over p (MXU matmul with the N1-point factor matrix)
    3. twiddle exp(-2pi i r q / N)  (q offset from lax.axis_index)
    4. all_to_all back: r sharded, q local
    5. local DFT over q
    6. (output_order='natural') third all_to_all + local transpose so
       device d holds the contiguous k chunk; 'transposed' skips it
       and returns X[N1*s + r] with r sharded — free, and enough for
       symmetric pipelines (e.g. |X|^2 spectrometry, convolution with
       a kernel stored in the same order).

The collectives ride the ICI (jax.lax.all_to_all inside shard_map);
each local DFT is a dense matmul on the MXU, so the compute term uses
the systolic array rather than a scalar butterfly network.
"""

from __future__ import annotations

import numpy as np

__all__ = ['sharded_fft', 'distributed_fft_local',
           'freq_sharded_dft', 'freq_chunk_dft_local']

from .ops import _P
# reuse the cached four-step factor matrices and the re/im-plane
# constant embedding
from ..ops.fft import _dft_matrices, _const_complex


def distributed_fft_local(x_loc, n1, n2, axis_name,
                          inverse=False, output_order='natural'):
    """Per-shard body (call inside shard_map): ``x_loc`` is this
    device's contiguous (..., N/D) chunk of the transform axis."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    d = jax.lax.axis_size(axis_name)
    if n1 % d or n2 % d:
        raise ValueError(
            "distributed fft needs D | N1 and D | N2 "
            "(N1=%d, N2=%d, D=%d)" % (n1, n2, d))
    lead = x_loc.shape[:-1]
    nb = len(lead)
    f1h, f2h, twh = _dft_matrices(n1, n2, inverse, 'c64')
    # (..., N1/D, N2): local rows p, full q
    x = x_loc.reshape(lead + (n1 // d, n2))
    # 1. split q into D chunks -> exchange -> all p local, q sharded
    x = x.reshape(lead + (n1 // d, d, n2 // d))
    x = lax.all_to_all(x, axis_name, split_axis=nb + 1,
                       concat_axis=nb, tiled=False)
    # all_to_all with explicit split/concat: result (..., N1, N2/D)
    x = x.reshape(lead + (n1, n2 // d))
    # 2. DFT over p (contraction with the N1-point factor matrix)
    y = jnp.einsum('...pq,pr->...rq', x,
                   _const_complex(f1h, jnp.complex64))
    # 3. twiddle: slice this shard's GLOBAL q columns from the cached
    # (n1, n2) twiddle matrix
    q0 = lax.axis_index(axis_name) * (n2 // d)
    tw = lax.dynamic_slice(
        _const_complex(twh, jnp.complex64),
        (0, q0), (n1, n2 // d))
    y = y * tw.astype(y.dtype)
    # 4. exchange back: split r -> concat q -> r sharded, full q
    y = y.reshape(lead + (d, n1 // d, n2 // d))
    y = lax.all_to_all(y, axis_name, split_axis=nb,
                       concat_axis=nb + 1, tiled=False)
    y = y.reshape(lead + (n1 // d, n2))
    # 5. DFT over q
    z = jnp.einsum('...rq,qs->...rs', y,
                   _const_complex(f2h, jnp.complex64))
    if output_order == 'transposed':
        # X[N1*s + r], r sharded: (..., N1/D, N2) as-is
        return z.reshape(lead + (n1 // d * n2,))
    # 6. natural order: redistribute s, transpose locally so device d
    # holds the contiguous k chunk [d*N/D, (d+1)*N/D)
    z = z.reshape(lead + (n1 // d, d, n2 // d))
    z = lax.all_to_all(z, axis_name, split_axis=nb + 1,
                       concat_axis=nb, tiled=False)
    z = z.reshape(lead + (n1, n2 // d))
    z = jnp.swapaxes(z, -1, -2)           # (..., N2/D, N1): k = N1 s + r
    return z.reshape(lead + (n1 * n2 // d,))


def sharded_fft(mesh, n, axis_name='sp', inverse=False,
                output_order='natural', n1=None, nbatch=0):
    """jit-ready distributed c2c FFT: input (..., N) complex with
    ``nbatch`` unsharded leading axes and the LAST axis sharded over
    ``axis_name``; unnormalized inverse like ops.fft.  Returns a
    function over global arrays (shard_map'd)."""
    from jax import shard_map
    if n1 is None:
        import math
        h = int(math.log2(n))
        if 1 << h != n:
            raise ValueError("sharded_fft requires power-of-two N")
        n1 = 1 << (h // 2)
    n2 = n // n1

    def local(x):
        return distributed_fft_local(x, n1, n2, axis_name,
                                     inverse=inverse,
                                     output_order=output_order)

    spec = _P(*([None] * nbatch + [axis_name]))
    return shard_map(local, mesh=mesh, in_specs=spec, out_specs=spec)


def freq_chunk_dft_local(x, n1, n2, axis_name, ndev, inverse=False):
    """Per-shard body of the CROSS-CHIP CHANNELIZER: from a REPLICATED
    (..., N) frame, device d computes ONLY its contiguous channel
    chunk k in [d*N/D, (d+1)*N/D) via the decomposed DFT — with ZERO
    collectives inside the frame ("Large-Scale DFT on TPUs",
    PAPERS.md).

    N = n1*n2, n = n2*p + q, k = n1*s + r: the n1-point DFT over p and
    the twiddle are k-chunk independent, and a contiguous k chunk is
    exactly an s-column chunk of the n2-point factor matrix (requires
    D | n2) — so the only per-device specialization is a column slice,
    and the F-stage shards over the mesh frequency axis for free.
    Contrast distributed_fft_local, which shards the INPUT and pays
    three all_to_alls; here the input is replicated (committed once,
    outside the compiled frame) and the mesh buys you an N*D-channel
    F-engine per N channels of per-chip work."""
    import jax.numpy as jnp
    from jax import lax

    if n2 % ndev:
        raise ValueError("freq-sharded dft needs D | N2 "
                         "(N2=%d, D=%d)" % (n2, ndev))
    lead = x.shape[:-1]
    f1h, f2h, twh = _dft_matrices(n1, n2, inverse, 'c64')
    xt = x.reshape(lead + (n1, n2))     # x[n2*p + q] -> [p, q]
    inner = jnp.einsum('...pq,pr->...rq', xt,
                       _const_complex(f1h, jnp.complex64))
    inner = inner * _const_complex(twh, jnp.complex64).astype(
        inner.dtype)
    # this device's s-columns of the n2-point factor matrix
    sc = n2 // ndev
    s0 = lax.axis_index(axis_name) * sc
    f2 = lax.dynamic_slice(_const_complex(f2h, jnp.complex64),
                           (0, s0), (n2, sc))
    chunk = jnp.einsum('...rq,qs->...rs', inner, f2)
    # k = n1*s + r: s-major flatten gives the contiguous k chunk
    chunk = jnp.swapaxes(chunk, -1, -2)
    return chunk.reshape(lead + (n1 * sc,))


def freq_sharded_dft(mesh, n, axis_name='sp', inverse=False, n1=None,
                     nbatch=0):
    """jit-ready frequency-sharded channelizer: input (..., N) complex
    REPLICATED over ``axis_name`` (``nbatch`` leading axes), output
    (..., N) with the channel axis sharded — device d holds channels
    [d*N/D, (d+1)*N/D) — and no collective anywhere in the lowered
    program (asserted by tests/test_correlate.py via the HLO-stats
    counters).  Returns a function over global arrays (shard_map'd)."""
    from jax import shard_map
    ndev = int(mesh.shape[axis_name])
    if n1 is None:
        import math
        h = int(math.log2(n))
        if 1 << h != n:
            raise ValueError("freq_sharded_dft requires power-of-two N")
        n1 = 1 << (h // 2)
    n2 = n // n1

    def local(x):
        return freq_chunk_dft_local(x, n1, n2, axis_name, ndev,
                                    inverse=inverse)

    in_spec = _P()      # replicated: the frame is committed whole,
    #                     before the compiled program runs
    out_spec = _P(*([None] * nbatch + [axis_name]))
    return shard_map(local, mesh=mesh, in_specs=in_spec,
                     out_specs=out_spec)
