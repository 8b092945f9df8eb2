"""Folding a time series at a pulsar's period, with an integer phase
rule, on the device.

A pulsar-timing backend sums every sample of a detected time series
into the phase bin of the rotation it fell in.  The period here is a
rational number of samples, ``pnum / pden`` (PSR J1903+0327 at
1.5625 MHz: 335923 / 100), so the phase of absolute sample n is the
residue ``(pden * n) mod pnum`` over ``pnum`` and its bin

    bin(n) = floor(nbin * ((pden * n) mod pnum) / pnum)

in integers throughout, exactly as a reference computes it (n may be
negative: Python's modulo is floored, so r0 lies in [0, pnum)).  A gulp
of ``nsamp`` samples that starts at absolute sample n0 needs one
number from the host, its start residue ``r0 = (pden * n0) mod pnum``
(:func:`residues`, exact in Python's integers): sample j of the gulp
has residue ``(r0 + pden * j) mod pnum``, which int32 holds.  The
residue is an argument of the gulp's program, so no gulp recompiles.

How it is summed without a scatter (:func:`fold_turns`, then
:func:`fold_bins`).  Cut the gulp at the rotations' starts: rotation
tau of the gulp starts at sample J_tau = ceil((tau pnum - r0) / pden),
offset f_tau = r0 + pden J_tau - tau pnum in [0, pden), and lasts at
most C = ceil(pnum / pden) samples.  Sample c of rotation tau falls in
bin floor(nbin (f_tau + pden c) / pnum), which differs from the
static g(c) = floor(nbin pden c / pnum) by delta in [0, M), M =
ceil(nbin pden / pnum) + 1 (two here).  So the rotations, sliced from
the series where they start (dynamic slices of C samples), are summed
into M partial rotations X_m[c] = sum over tau of x_tau[c] where
delta = m: elementwise work, one pass over the series; and the bins
are then a fixed 0/1 matrix away, X_m[c] landing in bin
(g(c) + m) mod nbin: one small matrix product at full float32
precision for a whole gulp (its operand is M C columns, not the
series).  Every sum is float32 on the vector unit; the matrix product
adds at most four nonzero terms a bin.

Where the cut runs (:func:`cut_path`).  On a TPU, for rows in whole
tiles of eight and a series in whole 128-lane columns, a Pallas kernel
reads the series once where it lies: over a grid of (row blocks,
rotations), rotation tau's 128-aligned window of W = 128 ceil((C +
127) / 128) samples is copied from HBM at b = clip(128 floor(J_tau /
128), 0, nsamp - W), rotated by J_tau - b lanes so that lane c holds
sample J_tau + c, weighted and added to the partial rotations, which
stay in VMEM over the rotations.  A sample before the series or past
its end lands on a lane whose weight is 0, so nothing is padded, and
no rotation is kept.  Elsewhere XLA cuts the rotations from the series
padded by C samples each side and sums them (the compiler keeps all of
them: nturn R C words).
"""

from __future__ import annotations

import functools
import math

import numpy as np

__all__ = ['FoldRule', 'residues', 'phase_bins', 'cut_path', 'fold_turns',
           'fold_bins', 'fold']

_INT32 = 2 ** 31
#: the kernel's VMEM for its blocks, of the 16 MiB Mosaic allows a
#: kernel by default: the window twice, the partial rotations twice
#: (the pipeline's two output buffers) and the weights
_VMEM_BYTES = 12 << 20


class FoldRule(object):
    """The static shape of the fold of ``nsamp`` samples into ``nbin``
    bins at a period of ``pnum / pden`` samples: the rotations a gulp
    can touch (``nturn``), the longest (``turn``), the bin classes
    (``nclass``) and the int32 bounds the arithmetic relies on."""

    def __init__(self, nsamp, nbin, pnum, pden):
        self.nsamp, self.nbin = int(nsamp), int(nbin)
        self.pnum, self.pden = int(pnum), int(pden)
        if min(self.nsamp, self.nbin, self.pnum, self.pden) < 1:
            raise ValueError('fold: nsamp, nbin and the period must be '
                             'positive')
        if self.nbin * (self.pnum + self.pden) >= _INT32 or \
                self.pden * self.nsamp + self.pnum >= _INT32:
            raise ValueError('fold: %d bins of a %d/%d period over %d '
                             'samples overflow int32'
                             % (self.nbin, self.pnum, self.pden,
                                self.nsamp))
        self.turn = -(-self.pnum // self.pden)
        self.nturn = (self.pnum - 1 + self.pden * (self.nsamp - 1)) \
            // self.pnum + 1
        self.nclass = -(-self.nbin * self.pden // self.pnum) + 1

    def key(self):
        return (self.nsamp, self.nbin, self.pnum, self.pden)


def residues(frame_offset, nframe, nsamp, pnum, pden, origin=0):
    """Start residues ``(pden * n0) mod pnum`` of ``nframe`` frames of
    ``nsamp`` samples from frame ``frame_offset`` on (n0 = frame *
    nsamp + ``origin``, which may be negative), exact in Python's
    integers, as int32."""
    return np.array([(int(pden) * ((int(frame_offset) + t) * int(nsamp)
                                   + int(origin))) % int(pnum)
                     for t in range(int(nframe))], np.int32)


def phase_bins(r0, nsamp, nbin, pnum, pden):
    """The bin of each of ``nsamp`` samples from start residue ``r0``:
    the rule itself, in numpy's int64."""
    j = np.arange(int(nsamp), dtype=np.int64)
    return (int(nbin) * ((int(r0) + int(pden) * j) % int(pnum))) \
        // int(pnum)


@functools.lru_cache(maxsize=8)
def _static_bins(key):
    """g(c): the bin of sample c of a rotation that starts at residue
    0, int32 (turn,)."""
    rule = FoldRule(*key)
    c = np.arange(rule.turn, dtype=np.int64)
    return ((rule.nbin * rule.pden * c) // rule.pnum).astype(np.int32)


def _turn_weights(r0, rule, lanes=None):
    """(start, w) of the gulp whose start residue is ``r0`` (an int32
    scalar, traced): where each rotation the gulp touches starts,
    start (nturn,) int32 (negative for the one it begins inside), and
    w (nturn, nclass, lanes) float32, ``lanes`` the turn by default:
    1 where sample c of rotation tau lies in the gulp and falls in bin
    g(c) + m (module docstring); 0 past the turn (sample c >= C of a
    rotation has residue f + D c >= P)."""
    import jax.numpy as jnp
    P, D, C = rule.pnum, rule.pden, rule.turn
    lanes = C if lanes is None else lanes
    g = np.pad(_static_bins(rule.key()), (0, lanes - C))
    r0 = jnp.asarray(r0, jnp.int32).reshape(())
    tau = jnp.arange(rule.nturn, dtype=jnp.int32)
    start = -((r0 - tau * P) // D)                  # ceil((tau P - r0) / D)
    off = r0 + D * start - tau * P                  # in [0, D)
    c = jnp.arange(lanes, dtype=jnp.int32)
    res = off[:, None] + D * c[None, :]             # (nturn, lanes)
    j = start[:, None] + c[None, :]
    valid = (res < P) & (j >= 0) & (j < rule.nsamp)
    delta = (rule.nbin * res) // P - jnp.asarray(g)[None, :]
    w = jnp.stack([(valid & (delta == m)).astype(jnp.float32)
                   for m in range(rule.nclass)], axis=1)
    return start, w


def _window(rule):
    """W: the 128-aligned window that holds a rotation shifted by up
    to 127 lanes."""
    return 128 * -(-(rule.turn + 127) // 128)


def _row_block(rows, rule):
    """The kernel's rows a block: the most, at most 128 and a multiple
    of 8 that divides ``rows``, whose blocks fit ``_VMEM_BYTES``; None
    where none does."""
    lanes = 4 * _window(rule)
    weights = 2 * 8 * -(-rule.nclass // 8) * lanes
    for rb in range(min(rows, 128) // 8 * 8, 0, -8):
        if rows % rb == 0 and \
                2 * rb * (1 + rule.nclass) * lanes + weights <= _VMEM_BYTES:
            return rb
    return None


def _on_tpu():
    import jax
    return jax.default_backend() == 'tpu'


def cut_path(rows, rule):
    """Which cut :func:`fold_turns` takes for ``rows`` rows of the
    series of ``rule``: 'kernel' on a TPU where the rows come in whole
    tiles of eight, the series in whole 128-lane columns at least a
    window long and the blocks fit VMEM; 'xla' elsewhere (module
    docstring)."""
    ok = (_on_tpu() and rows % 8 == 0 and rule.nsamp % 128 == 0
          and rule.nsamp >= _window(rule)
          and _row_block(rows, rule) is not None)
    return 'kernel' if ok else 'xla'


def _kernel_cut(x, start, w, rule, interpret=False):
    """The partial rotations (nclass, R, W) of rows ``x`` (R, nsamp)
    float32, whose rotations start at ``start`` (nturn,) int32 and
    weigh ``w`` (nturn, nclass, W): one Pallas kernel over (row blocks,
    rotations), the rotations its reduction axis (module docstring).
    A rotation's window is copied from HBM while the one before it is
    summed."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, nsamp = x.shape
    nturn, nclass, W = w.shape
    rb = _row_block(rows, rule)

    def kernel(start_ref, x_hbm, w_ref, o_ref, buf, sem):
        i, t = pl.program_id(0), pl.program_id(1)

        def base(tau):
            return pl.multiple_of(
                jnp.clip(start_ref[tau] // 128 * 128, 0, nsamp - W), 128)

        def copy(tau, slot):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(pl.multiple_of(i * rb, 8), rb),
                         pl.ds(base(tau), W)],
                buf.at[slot], sem.at[slot])

        slot = t % 2

        @pl.when(t == 0)
        def _():
            copy(t, slot).start()
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(t + 1 < nturn)
        def _():
            copy(t + 1, 1 - slot).start()

        copy(t, slot).wait()
        # lane c <- lane (c + start - base) mod W, a roll by W - that
        shift = W - (start_ref[t] - base(t))
        shift = jnp.where(shift >= W, shift - W, shift)

        def strip(k, carry):
            r = pl.ds(pl.multiple_of(k * 8, 8), 8)
            v = pltpu.roll(buf[slot, r, :], shift, axis=1)
            for m in range(nclass):
                o_ref[m, r, :] += v * w_ref[0, m:m + 1, :]
            return carry
        jax.lax.fori_loop(0, rb // 8, strip, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // rb, nturn),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((1, nclass, W), lambda i, t, s: (t, 0, 0))],
        out_specs=pl.BlockSpec((nclass, rb, W), lambda i, t, s: (0, i, 0)),
        scratch_shapes=[pltpu.VMEM((2, rb, W), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nclass, rows, W), jnp.float32),
        name='fold_cut',
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret,
    )(start, x, w)


def fold_turns(x, r0, rule):
    """Rows ``x`` (R, nsamp), real, and the gulp's start residue
    ``r0`` (an int32 scalar, traced) -> the partial rotations
    (nclass, R, turn) float32, by the cut :func:`cut_path` names
    (module docstring)."""
    import jax
    import jax.numpy as jnp
    C = rule.turn
    x = x.astype(jnp.float32)
    if cut_path(x.shape[0], rule) == 'kernel':
        start, w = _turn_weights(r0, rule, _window(rule))
        return _kernel_cut(x, start, w, rule)[..., :C]
    start, w = _turn_weights(r0, rule)
    # the rotations where they start: C samples of padding either side
    # keep every slice inside the array (dynamic_slice would clamp)
    xp = jnp.pad(x, ((0, 0), (C, C)))
    turns = jax.vmap(lambda s: jax.lax.dynamic_slice_in_dim(
        xp, s, C, axis=1))(start + C)               # (nturn, R, C)
    # a sum over rotations on the vector unit (an einsum would make it
    # a batch of tiny matrix products, one a rotation sample)
    return (turns[:, None] * w[:, :, None, :]).sum(axis=0)


def fold_bins(parts, rule):
    """Partial rotations (..., nclass, R, turn) -> profiles
    (..., R, nbin): the fixed 0/1 matrix product, at full float32
    precision.  The matrix is made in the program from the bins g(c),
    not folded into it as a constant (nclass turn nbin words)."""
    import jax
    import jax.numpy as jnp
    g = jnp.asarray(_static_bins(rule.key()))
    m = jnp.arange(rule.nclass, dtype=jnp.int32)[:, None, None]
    b = jnp.arange(rule.nbin, dtype=jnp.int32)[None, None, :]
    G = (b == (g[None, :, None] + m) % rule.nbin).astype(jnp.float32)
    return jnp.einsum('...mrc,mcb->...rb', parts, G,
                      precision=jax.lax.Precision.HIGHEST)


def fold(x, r0, rule):
    """``x`` (..., nsamp) real and the start residue ``r0`` -> the
    profiles (..., nbin) float32 of every row."""
    lead = x.shape[:-1]
    rows = int(math.prod(lead)) if lead else 1
    parts = fold_turns(x.reshape(rows, rule.nsamp), r0, rule)
    return fold_bins(parts, rule).reshape(lead + (rule.nbin,))
