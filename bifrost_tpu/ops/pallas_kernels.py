"""Pallas TPU kernels for hot ops.

The reference hand-writes CUDA kernels where library code falls short
(reference: src/linalg_kernels.cu, src/fdmt.cu, ...).  The TPU analogue
is Pallas.  XLA's fusion already covers most of this framework's chains
(see blocks/fused.py), so Pallas is reserved for cases where explicit
tiling wins; this module establishes the pattern with a Stokes-detect
kernel operating on re/im planes (complex refs are avoided — TPU Pallas
works on real tiles) and is gated by :func:`available`.

Enable in stages with ``BF_USE_PALLAS=1`` (off by default: XLA's fused
detect is the default path; the kernel has no chip timing yet).
"""

from __future__ import annotations

import os

__all__ = ['available', 'stokes_detect', 'xcorr_herm', 'xcorr_cross',
           'beamform_int8', 'beamform_bf16', 'beamform_detect',
           'beam_wide_weights', 'beam_time_tile',
           'ring_permute']

_checked = None


def available():
    """True where Pallas kernels compile natively through Mosaic — the
    TPU backend.  Elsewhere False: tests run the kernels with
    ``interpret=True`` instead (selection by platform).  On a TPU a
    trivial kernel is compiled and run once; a failure THERE is an
    installation fault and raises with its cause instead of quietly
    sending every selection down the XLA path."""
    global _checked
    if _checked is not None:
        return _checked
    import jax
    if jax.default_backend() != 'tpu':
        _checked = False
        return _checked
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def k(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.ones((8, 128), jnp.float32)
    try:
        out = pl.pallas_call(
            k, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))(x)
        total = float(out.sum())
    except Exception as e:
        raise RuntimeError(
            'Pallas cannot compile or run a trivial kernel on this TPU '
            'backend (%s: %s)' % (type(e).__name__, e)) from e
    if abs(total - 2 * 8 * 128) > 1e-3:
        raise RuntimeError('Pallas probe kernel returned %r, expected '
                           '%r, on this TPU backend' % (total, 2 * 8 * 128))
    _checked = True
    return _checked


def enabled():
    flag = os.environ.get('BF_USE_PALLAS', '').strip().lower()
    return flag in ('1', 'true', 'yes', 'on') and available()


def stokes_detect(xr, xi, yr, yi, tile=512, time_tile=256,
                  interpret=False):
    """Stokes I,Q,U,V from dual-pol complex voltages given as re/im
    planes, as a tiled Pallas kernel.

    xr/xi/yr/yi: (T, F) float32.  Returns (T, 4, F) float32.
    (reference math: blocks/detect.py stokes mode)

    Tiled over BOTH axes: four (time_tile, tile) input blocks plus the
    (time_tile, 4, tile) output block, double-buffered, are 8 MB at
    the defaults — inside Mosaic's 16 MB scoped-VMEM limit whatever
    the gulp's frame count (a whole-T block is refused from T=1024).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, F = xr.shape
    tile = min(tile, F)
    if F % tile:
        tile = F
    time_tile = min(time_tile, T)
    if T % time_tile:
        time_tile = T

    def kernel(xr_ref, xi_ref, yr_ref, yi_ref, o_ref):
        a_r = xr_ref[...]
        a_i = xi_ref[...]
        b_r = yr_ref[...]
        b_i = yi_ref[...]
        xx = a_r * a_r + a_i * a_i
        yy = b_r * b_r + b_i * b_i
        # x * conj(y)
        xy_r = a_r * b_r + a_i * b_i
        xy_i = a_i * b_r - a_r * b_i
        o_ref[:, 0, :] = xx + yy
        o_ref[:, 1, :] = xx - yy
        o_ref[:, 2, :] = 2.0 * xy_r
        o_ref[:, 3, :] = -2.0 * xy_i

    grid = (T // time_tile, F // tile)
    spec = pl.BlockSpec((time_tile, tile), lambda i, j: (i, j))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec, spec],
        out_specs=pl.BlockSpec((time_tile, 4, tile),
                               lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((T, 4, F), jnp.float32),
        interpret=interpret,
    )(xr, xi, yr, yi)
    return out


# shared scaffolding for the correlation kernels: contract the time
# axis of (T, n) operands (lhs-transposed) with exact int32
# accumulation; interpret-mode default keeps off-TPU probe races
# functional (slowly) instead of erroring
_XCORR_DN = (((0,), (0,)), ((), ()))


def _chan_major(x):
    """(T, F, n) -> (F, T, n).  The per-channel kernels run one
    frequency channel per program; Mosaic requires a block's last two
    dims to equal the array's or be multiples of (8, 128), so the
    channel axis cannot sit second-minor with block size 1 (the
    (T, 1, n) block is refused at lowering).  Leading, it can: block
    (1, T, n).  Costs one XLA transpose of the planes."""
    import jax.numpy as jnp
    return jnp.transpose(x, (1, 0, 2))


def _dot_i32(a, b):
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, _XCORR_DN,
                               preferred_element_type=jnp.int32)


def _xcorr_interpret(interpret):
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() != 'tpu'


def xcorr_herm(re, im, interpret=None):
    """Fused int8 Hermitian auto-correlation, one channel per program.

    Per frequency channel: the three Hermitian int8 MXU dots
    (rr, ii, K with K = im^T.re contracting time) accumulate in VMEM
    int32 and the visibility epilogue (re = rr+ii, im = K - K^T) is
    applied before anything returns to HBM — so neither the widened
    (2n)^2 gram intermediate nor the three separate int32 products are
    ever materialized in HBM, and each visibility block is written
    exactly once.  This is the TPU expression of the reference's
    hand-kernel move (dp4a cherk with register accumulation,
    src/linalg_kernels.cu:55); it races in the measured xcorr
    selection (ops.linalg) and is dropped automatically wherever
    Mosaic rejects it (e.g. shapes whose per-channel footprint exceeds
    VMEM).

    re, im: (T, F, n) int8 -> (F, n, n) complex64 visibilities.
    For cross blocks (different i/j station sets) see xcorr_cross.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, F, n = re.shape
    interpret = _xcorr_interpret(interpret)

    def kernel(re_ref, im_ref, or_ref, oi_ref):
        r = re_ref[0]
        i = im_ref[0]
        rr = _dot_i32(r, r)
        ii = _dot_i32(i, i)
        k = _dot_i32(i, r)
        or_ref[0] = (rr + ii).astype(jnp.float32)
        oi_ref[0] = (k - k.T).astype(jnp.float32)

    spec_in = pl.BlockSpec((1, T, n), lambda f: (f, 0, 0))
    spec_out = pl.BlockSpec((1, n, n), lambda f: (f, 0, 0))
    vr, vi = pl.pallas_call(
        kernel,
        grid=(F,),
        in_specs=[spec_in, spec_in],
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((F, n, n), jnp.float32)] * 2,
        interpret=interpret,
    )(_chan_major(re), _chan_major(im))
    return vr + 1j * vi


def xcorr_cross(re_i, im_i, re_j, im_j, interpret=None):
    """Fused int8 cross-correlation, one channel per program (the
    station-sharded mesh correlator's row-block x gathered-columns
    form).  vis[f, a, b] = sum_t x_i[t, f, a] * conj(x_j[t, f, b]):
    four int8 MXU dots accumulate in VMEM int32 and the complex
    epilogue (rr+ii, ir-ri) is fused — no int32 products reach HBM.

    re_i, im_i: (T, F, n_i) int8;  re_j, im_j: (T, F, n_j) int8
    -> (F, n_i, n_j) complex64.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, F, ni = re_i.shape
    nj = re_j.shape[-1]
    interpret = _xcorr_interpret(interpret)

    def kernel(ri_ref, ii_ref, rj_ref, ij_ref, or_ref, oi_ref):
        ri = ri_ref[0]
        imi = ii_ref[0]
        rj = rj_ref[0]
        imj = ij_ref[0]
        rr = _dot_i32(ri, rj)
        ii = _dot_i32(imi, imj)
        ir = _dot_i32(imi, rj)
        ri_ = _dot_i32(ri, imj)
        or_ref[0] = (rr + ii).astype(jnp.float32)
        oi_ref[0] = (ir - ri_).astype(jnp.float32)

    spec_i = pl.BlockSpec((1, T, ni), lambda f: (f, 0, 0))
    spec_j = pl.BlockSpec((1, T, nj), lambda f: (f, 0, 0))
    spec_out = pl.BlockSpec((1, ni, nj), lambda f: (f, 0, 0))
    vr, vi = pl.pallas_call(
        kernel,
        grid=(F,),
        in_specs=[spec_i, spec_i, spec_j, spec_j],
        out_specs=[spec_out, spec_out],
        out_shape=[jax.ShapeDtypeStruct((F, ni, nj), jnp.float32)] * 2,
        interpret=interpret,
    )(*(_chan_major(x) for x in (re_i, im_i, re_j, im_j)))
    return vr + 1j * vi


# ---------------------------------------------------------------------------
# coherent-beamformer kernels (the quantized beamform/correlate engine,
# ops/beamform.py; recipe papers: "The Tensor-Core Beamformer"
# arXiv:2505.03269, "GPU-Powered Coherent Beamforming" arXiv:1412.4907)
# ---------------------------------------------------------------------------

#: contract the station axis (dim 1 of both operands): (T, N) x (B, N)
#: -> (T, B)
_BEAM_DN = (((1,), (1,)), ((), ()))


def _dot_beam(a, b, acc):
    import jax
    return jax.lax.dot_general(a, b, _BEAM_DN,
                               preferred_element_type=acc)


def beamform_int8(wr, wi, re, im, interpret=None):
    """Fused int8 coherent beamform, one frequency channel per program.

    Per channel: the four int8 MXU dots of the complex product
    y[t, b] = sum_n w[b, n] * x[t, n] (yr = r.wr^T - i.wi^T,
    yi = r.wi^T + i.wr^T) accumulate in VMEM int32 and each (T, B)
    beam block is written exactly once — the TPU expression of the
    tensor-core beamformer's fused cgemm (arXiv:2505.03269; the
    reference's dp4a cherk analogue, src/linalg_kernels.cu:55).  The
    int8 voltage planes are the ci8 ring's device representation, so
    no f32 voltages ever materialize in HBM.

    wr, wi: (B, N) int8 quantized weight planes;
    re, im: (T, F, N) int8 voltage planes
    -> (yr, yi): (T, F, B) int32 planes (EXACT integer accumulation —
    the caller applies the weight dequantization scale).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, F, N = re.shape
    B = wr.shape[0]
    interpret = _xcorr_interpret(interpret)

    def kernel(wr_ref, wi_ref, re_ref, im_ref, or_ref, oi_ref):
        r = re_ref[0]
        i = im_ref[0]
        wr_ = wr_ref[...]
        wi_ = wi_ref[...]
        or_ref[0] = (_dot_beam(r, wr_, jnp.int32) -
                     _dot_beam(i, wi_, jnp.int32))
        oi_ref[0] = (_dot_beam(r, wi_, jnp.int32) +
                     _dot_beam(i, wr_, jnp.int32))

    spec_w = pl.BlockSpec((B, N), lambda f: (0, 0))
    spec_x = pl.BlockSpec((1, T, N), lambda f: (f, 0, 0))
    spec_o = pl.BlockSpec((1, T, B), lambda f: (f, 0, 0))
    yr, yi = pl.pallas_call(
        kernel,
        grid=(F,),
        in_specs=[spec_w, spec_w, spec_x, spec_x],
        out_specs=[spec_o, spec_o],
        out_shape=[jax.ShapeDtypeStruct((F, T, B), jnp.int32)] * 2,
        interpret=interpret,
    )(wr, wi, _chan_major(re), _chan_major(im))
    return _chan_major(yr), _chan_major(yi)


def beamform_bf16(wr, wi, re, im, interpret=None):
    """Single-pass bf16 beamform, one channel per program: the same
    four dots as :func:`beamform_int8` but in bf16 with f32
    accumulation — full MXU rate, ~2^-8 input rounding.  LOSSY by
    construction: races only under a widened accuracy class
    (ops/beamform.py) or a forced BF_BEAM_IMPL.

    wr, wi: (B, N) float32 weight planes (cast to bf16 in VMEM);
    re, im: (T, F, N) int8 (or float) voltage planes
    -> (yr, yi): (T, F, B) float32 planes.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, F, N = re.shape
    B = wr.shape[0]
    interpret = _xcorr_interpret(interpret)

    def kernel(wr_ref, wi_ref, re_ref, im_ref, or_ref, oi_ref):
        r = re_ref[0].astype(jnp.bfloat16)
        i = im_ref[0].astype(jnp.bfloat16)
        wr_ = wr_ref[...].astype(jnp.bfloat16)
        wi_ = wi_ref[...].astype(jnp.bfloat16)
        or_ref[0] = (_dot_beam(r, wr_, jnp.float32) -
                     _dot_beam(i, wi_, jnp.float32))
        oi_ref[0] = (_dot_beam(r, wi_, jnp.float32) +
                     _dot_beam(i, wr_, jnp.float32))

    spec_w = pl.BlockSpec((B, N), lambda f: (0, 0))
    spec_x = pl.BlockSpec((1, T, N), lambda f: (f, 0, 0))
    spec_o = pl.BlockSpec((1, T, B), lambda f: (f, 0, 0))
    yr, yi = pl.pallas_call(
        kernel,
        grid=(F,),
        in_specs=[spec_w, spec_w, spec_x, spec_x],
        out_specs=[spec_o, spec_o],
        out_shape=[jax.ShapeDtypeStruct((F, T, B), jnp.float32)] * 2,
        interpret=interpret,
    )(wr, wi, _chan_major(re), _chan_major(im))
    return _chan_major(yr), _chan_major(yi)


#: frames of one program of :func:`beamform_detect` on the chip: the
#: int32 beam sums of a tile (four sections of 7 x 128 columns at 864
#: beams) and their float32 squares stay inside the 16 MiB of scoped
#: VMEM, and 512 / 16 = 32 output rows fill one tile of 8-bit words
BEAM_TIME_TILE = 512


def beam_time_tile(ntime, rfactor, out_itemsize, target=None):
    """Frames a program of :func:`beamform_detect` takes: the largest
    count of whole output tiles (32 rows of 8-bit words, 8 of wider
    ones, ``rfactor`` frames each) that divides ``ntime`` and stays
    at or under ``target`` (BEAM_TIME_TILE), else all of them."""
    target = BEAM_TIME_TILE if target is None else int(target)
    unit = rfactor * (32 if out_itemsize == 1 else 8)
    if ntime % unit:
        return ntime
    tile = max(unit, target - target % unit)
    while ntime % tile:
        tile -= unit
    return tile


def beam_wide_weights(wr, wi, dtype=None):
    """The weight operand of :func:`beamform_detect` (numpy):
    (F, 2, B, S) planes of dual-polarisation weights, F one (one set
    for every channel) or the channels' own, as (F, 4 S, 4 Bp) with
    Bp the beams padded to whole lanes of 128.  Rows follow the
    kernel's operand z = [re | im], each half in the gulp's own
    (station, pol) order; columns are the four sections xr, xi, yr,
    yi.  A row of the other polarisation holds zeros: the gulp's
    samples are multiplied as they lie, and the MXU does twice the
    products that count, where taking the polarisations apart would
    cost a pass of the lanes.  Integer planes keep their type (int8:
    -wi must fit, so quantize_weights clips at 127); float planes
    come as ``dtype``."""
    import numpy as np
    nf, npol, nbeam, nstand = wr.shape
    if npol != 2:
        raise ValueError('dual-polarisation weights wanted, got %d'
                         % npol)
    bp = -(-nbeam // 128) * 128
    w = np.zeros((nf, 2, nstand, 2, 4, bp), dtype or wr.dtype)
    for p in range(2):
        r = np.swapaxes(wr[:, p], 1, 2)           # (F, S, B)
        i = np.swapaxes(wi[:, p], 1, 2)
        w[:, 0, :, p, 2 * p, :nbeam] = r
        w[:, 1, :, p, 2 * p, :nbeam] = -i
        w[:, 0, :, p, 2 * p + 1, :nbeam] = i
        w[:, 1, :, p, 2 * p + 1, :nbeam] = r
    return w.reshape(nf, 4 * nstand, 4 * bp)


def beamform_detect(xw, w, nchan, nbeam, rfactor, stokes='stokes_i',
                    scale=1.0, quantize=None, time_tile=None,
                    interpret=None):
    """Beamform, detect, integrate and (optionally) requantise a
    dual-polarisation ci8 gulp in one kernel, a channel and a tile of
    time a program: the beam voltages exist in VMEM alone.

    xw: the gulp's int16 words (low byte re, high byte im) as rows of
    frames, ``(T, nchan * S * 2)``: (freq, station, pol) along a row,
    the host's own order.  w: :func:`beam_wide_weights`,
    ``(nchan or 1, 4 S, 4 Bp)``, int8 (exact int32 sums on the MXU) or
    bfloat16 (one pass, float32 sums: lossy by the weights'
    rounding).  Per program: the words are split by sign-extending
    shifts, z = [re | im] meets the channel's weights in four dots
    (one a section), the sums are squared in float32, ``rfactor``
    frames are added, the result is multiplied by ``scale`` and, with
    ``quantize`` = (lo, hi, dtype), rounded and clipped.

    ``stokes``: 'stokes_i' (one plane) or 'stokes' (I, Q, U, V).
    Returns (T // rfactor, nchan, nst, nbeam), float32 or
    ``quantize``'s dtype.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    T, row = xw.shape
    if row % nchan or w.shape[1] != 2 * (row // nchan) or \
            w.shape[0] not in (1, nchan):
        raise ValueError('words %s and weights %s do not agree on %d '
                         'channels' % (xw.shape, w.shape, nchan))
    if stokes not in ('stokes_i', 'stokes'):
        raise ValueError('stokes_i or stokes, got %r' % (stokes,))
    if T % rfactor:
        raise ValueError('rfactor %d does not divide T=%d'
                         % (rfactor, T))
    sp = row // nchan                           # words a (frame, channel)
    bp = w.shape[2] // 4
    nst = 1 if stokes == 'stokes_i' else 4
    odt = jnp.float32 if quantize is None else jnp.dtype(quantize[2])
    tt = beam_time_tile(T, rfactor, jnp.dtype(odt).itemsize, time_tile)
    if T % tt or tt % rfactor:
        raise ValueError('time tile %d does not fit T=%d, rfactor=%d'
                         % (tt, T, rfactor))
    rows = tt // rfactor
    interpret = _xcorr_interpret(interpret)
    scale = float(scale)
    exact = jnp.issubdtype(w.dtype, jnp.integer)
    acc = jnp.int32 if exact else jnp.float32
    own = w.shape[0] == nchan

    def kernel(x_ref, w_ref, o_ref):
        v = x_ref[...].astype(jnp.int32)                  # (tt, sp)
        z = jnp.concatenate([(v << 24) >> 24, v >> 8], axis=1)
        z = z.astype(jnp.int8) if exact else \
            z.astype(jnp.float32).astype(jnp.bfloat16)

        def section(j):
            y = jax.lax.dot_general(
                z, w_ref[0, :, j * bp:(j + 1) * bp],
                (((1,), (0,)), ((), ())), preferred_element_type=acc)
            return y.astype(jnp.float32)                  # (tt, bp)

        if nst == 1:
            planes = [sum(section(j) ** 2 for j in range(4))]
        else:
            xr, xi, yr, yi = (section(j) for j in range(4))
            xx, yy = xr * xr + xi * xi, yr * yr + yi * yi
            planes = [xx + yy, xx - yy,
                      2.0 * (xr * yr + xi * yi),          # x conj(y)
                      -2.0 * (xi * yr - xr * yi)]
        for k, plane in enumerate(planes):
            # (tt, bp) -> (rows, rfactor, bp): the lanes stay, so the
            # reshape splits the leading dimension alone
            out = plane.reshape(rows, rfactor, bp).sum(axis=1) * scale
            if quantize is not None:
                out = jnp.clip(jnp.round(out), quantize[0], quantize[1]) \
                    .astype(jnp.int32)
            o_ref[:, k * bp:(k + 1) * bp] = out.astype(odt)

    out = pl.pallas_call(
        kernel,
        grid=(nchan, T // tt),
        in_specs=[pl.BlockSpec((tt, sp), lambda f, i: (i, f)),
                  pl.BlockSpec((1,) + tuple(w.shape[1:]),
                               (lambda f, i: (f, 0, 0)) if own else
                               (lambda f, i: (0, 0, 0)))],
        out_specs=pl.BlockSpec((rows, nst * bp), lambda f, i: (i, f)),
        out_shape=jax.ShapeDtypeStruct(
            (T // rfactor, nchan * nst * bp), odt),
        interpret=interpret,
        name='beamform_detect',
    )(xw, w)
    return out.reshape(T // rfactor, nchan, nst, bp)[..., :nbeam]


def fdmt_step(d1, d2, passthrough, rows_hi_max, sgn, T, interpret=False):
    """Build a Pallas kernel for one FDMT merge step.

    The step computes, for each output (subband s, delay d) row,
    ``out[s,d,t] = lo[2s, d1[s,d], t] + hi[rows_hi[s], d2[s,d], t + sgn*d1[s,d]]``
    with zero outside the valid time range — a gather+add along the
    lane-contiguous time axis that XLA lowers as a slow general gather
    (SURVEY.md §7 hard part d; reference CUDA kernel: src/fdmt.cu:53-96).

    Here the delay tables ride scalar prefetch (SMEM), block index maps
    pick the subband rows (so each subband's rows DMA once and stay in
    VMEM across its nd_out programs), and the per-row time shift is a
    lane roll + mask on the VPU.

    d1/d2: (nout, nd_out) int32; passthrough: (nout,) int32;
    rows_hi_max: nchan_cur-1 (clamp for odd tails); sgn: +-1; T: logical
    time length (lane padding beyond T is masked).
    Returns fn(lo_hi_state (nchan_cur, nd_cur, Tp)) -> (nout, nd_out, Tp).
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nout, nd_out = d1.shape

    # One program per output subband: its lo/hi rows DMA to VMEM once,
    # then a fori_loop emits all nd_out delay rows (full-(nd,T) blocks
    # keep the TPU tiling constraint — second-minor block dims must be
    # full-size or 8-divisible).
    def kernel(d1_ref, d2_ref, pt_ref, lo_ref, hi_ref, o_ref):
        s = pl.program_id(0)

        def body(d, carry):
            d1v = d1_ref[s, d]
            d2v = d2_ref[s, d]
            a = lo_ref[0, pl.ds(d1v, 1), :]          # (1, Tp)
            b = hi_ref[0, pl.ds(d2v, 1), :]
            shift = sgn * d1v
            rolled = pltpu.roll(b, -shift, axis=1)   # rolled[t]=b[t+shift]
            tt = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
            ok = (tt + shift >= 0) & (tt + shift <= T - 1)
            res = a + jnp.where(ok, rolled, 0.0)
            res = jnp.where(pt_ref[s] != 0, a, res)
            o_ref[0, pl.ds(d, 1), :] = res
            return carry

        jax.lax.fori_loop(0, nd_out, body, 0)

    def fn(state):
        nchan_cur, nd_cur, Tp = state.shape
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nout,),
            in_specs=[
                pl.BlockSpec((1, nd_cur, Tp),
                             lambda s, *_: (2 * s, 0, 0)),
                pl.BlockSpec((1, nd_cur, Tp),
                             lambda s, *_: (
                                 jnp.minimum(2 * s + 1, rows_hi_max),
                                 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, nd_out, Tp),
                                   lambda s, *_: (s, 0, 0)),
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((nout, nd_out, Tp),
                                           jnp.float32),
            interpret=interpret,
        )(jnp.asarray(d1), jnp.asarray(d2),
          jnp.asarray(passthrough, jnp.int32), state, state)

    return fn


def ring_permute(x, axis_name, ndev):
    """One correlator corner-turn ring hop as an explicit remote DMA:
    this device's whole block is DMA'd to its right neighbour
    ((i+1) % D over the ``axis_name`` ring), following the classic
    Pallas right-permute collective (SNIPPETS.md [3]).  Call inside
    shard_map over ``axis_name`` on a real TPU mesh; the send and
    receive ride dedicated DMA semaphores so hops can overlap the
    X-engine compute of already-landed chunks.

    parallel.corner_turn composes D-1 of these hops into the full
    time-sharded -> channel-sharded redistribution and races the
    composition against XLA's native all_to_all lowering (family
    ``corner_turn``) — the ring form wins when the all_to_all's
    packetization fights the gulp layout, and loses silently (it is
    never the unmeasured default) when it doesn't.
    """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(in_ref, out_ref, send_sem, recv_sem):
        my_id = jax.lax.axis_index(axis_name)
        dst = jax.lax.rem(my_id + 1, ndev)
        # the destination by its index along the ring's mesh axis (a
        # tuple with the LOGICAL id type is refused by jax 0.9.0)
        copy = pltpu.make_async_remote_copy(
            src_ref=in_ref, dst_ref=out_ref,
            send_sem=send_sem, recv_sem=recv_sem,
            device_id={axis_name: dst},
            device_id_type=pltpu.DeviceIdType.MESH)
        copy.start()
        copy.wait()

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.DMA],
        # no collective_id: jax 0.9.0 takes one only from kernels
        # that use the barrier semaphore, and this one does not
        compiler_params=pltpu.CompilerParams(has_side_effects=True),
    )(x)
