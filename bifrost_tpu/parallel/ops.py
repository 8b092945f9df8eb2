"""Sharded hot ops over an ICI mesh (shard_map + XLA collectives).

Parallelism mapping from the reference's model (SURVEY.md §2.9) to TPU:

- pipeline (thread-per-block)      -> unchanged, host side ("pp")
- intra-op CUDA grid               -> XLA on one chip
- multi-GPU per-block placement    -> shard the block's op over a Mesh:
    * time/gulp axis over 'sp' (data/sequence parallel; FIR history
      crosses shard boundaries via lax.ppermute halo exchange — the
      ring-attention-style neighbor pattern)
    * antenna axis over 'tp' (tensor parallel; beamforming GEMM partial
      sums meet in a psum, correlation all_gathers the antenna axis)
- multi-node UDP/RDMA streams      -> DCN ring bridge (io.bridge)

The ``_local_*`` functions are the per-shard bodies; the ``sharded_*``
wrappers and the flagship :func:`spectrometer_step` compose the SAME
bodies, so the collective patterns live in exactly one place.
"""

from __future__ import annotations

__all__ = ['sharded_spectrometer', 'sharded_beamform', 'sharded_correlate',
           'sharded_fdmt',
           'sharded_fir', 'spectrometer_step']


def _P(*args):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*args)


# ---------------------------------------------------------------------------
# per-shard bodies (shared by the sharded_* wrappers and spectrometer_step)
# ---------------------------------------------------------------------------

def _local_fir_stateful(x, coeffs, state, axis_name, decim=1):
    """Causal FIR along the (sharded) leading time axis.  ``state`` holds
    the replicated inter-gulp history (the previous gulp's final ntap-1
    frames) consumed by shard 0; interior shard boundaries exchange halos
    via ppermute — the sequence-parallel pattern (reference op keeps
    inter-gulp state host-side: src/fir.cu:143-316).  Returns
    ``(y, new_state)``; ``new_state`` is this gulp's global final ntap-1
    frames, replicated to every shard."""
    import jax
    import jax.numpy as jnp
    ntap = coeffs.shape[0]
    if ntap == 1:
        y = coeffs[0] * x
        return (y[::decim] if decim > 1 else y), state
    axis_size_ = jax.lax.axis_size(axis_name)
    halo = x[-(ntap - 1):]
    perm = [(i, (i + 1) % axis_size_) for i in range(axis_size_)]
    left = jax.lax.ppermute(halo, axis_name, perm)
    idx = jax.lax.axis_index(axis_name)
    left = jnp.where(idx == 0, state.astype(x.dtype), left)
    xp = jnp.concatenate([left, x], axis=0)
    out = jnp.zeros_like(x)
    for t in range(ntap):
        out = out + coeffs[t] * xp[ntap - 1 - t: xp.shape[0] - t]
    if decim > 1:
        out = out[::decim]
    # New state = the LAST shard's halo; a masked psum (rather than
    # all_gather + index) so shard_map can prove the result replicated.
    mask = (idx == axis_size_ - 1).astype(halo.dtype)
    new_state = jax.lax.psum(halo * mask, axis_name)
    return out, new_state


def _local_fir(x, coeffs, axis_name):
    """Stateless wrapper over :func:`_local_fir_stateful` (zero initial
    history; any unused all_gather is dead-code-eliminated by XLA)."""
    import jax.numpy as jnp
    ntap = coeffs.shape[0]
    if ntap == 1:
        return coeffs[0] * x
    state = jnp.zeros((ntap - 1,) + x.shape[1:], x.dtype)
    y, _ = _local_fir_stateful(x, coeffs, state, axis_name)
    return y


def _local_stokes(s):
    """(T, P=2, ...) complex -> (T, 4, ...) Stokes I,Q,U,V."""
    import jax.numpy as jnp
    x, y = s[:, 0], s[:, 1]
    xx = jnp.real(x) ** 2 + jnp.imag(x) ** 2
    yy = jnp.real(y) ** 2 + jnp.imag(y) ** 2
    xy = x * jnp.conj(y)
    return jnp.stack([xx + yy, xx - yy,
                      2 * jnp.real(xy), -2 * jnp.imag(xy)], axis=1)


def _local_beamform(w, v, ant_axis_name):
    """(B, A/tp) x (T, A/tp, F) -> (T, B, F): partial GEMM + psum
    (reference op: bfLinAlgMatMul beamform, src/linalg.cu:877)."""
    import jax
    import jax.numpy as jnp
    part = jnp.einsum('ba,taf->tbf', w, v,
                      preferred_element_type=jnp.complex64)
    return jax.lax.psum(part, ant_axis_name)


def _local_correlate(v, ant_axis_name, time_axis_name):
    """(T/sp, A/tp, F) -> (F, A/tp, A): each rank computes its antenna-row
    block against the all_gathered antenna axis, integrated over time
    shards (reference op: bfLinAlgMatMul a·a^H, src/linalg.cu:877)."""
    import jax
    import jax.numpy as jnp
    vfull = jax.lax.all_gather(v, ant_axis_name, axis=1, tiled=True)
    part = jnp.einsum('taf,tbf->fab', v, jnp.conj(vfull),
                      preferred_element_type=jnp.complex64)
    return jax.lax.psum(part, time_axis_name)


# ---------------------------------------------------------------------------
# shard_map wrappers
# ---------------------------------------------------------------------------

def sharded_spectrometer(mesh, time_axis_name='sp'):
    """FFT→Stokes-detect→integrate over gulps whose time axis is sharded
    across the mesh.  Input (T, P, F) complex; output (F', 4) f32 spectra
    integrated over all time shards (psum over the time axis)."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map

    def local_step(v):
        s = jnp.fft.fft(v, axis=-1)
        stokes = jnp.moveaxis(_local_stokes(s), 1, -1)
        return jax.lax.psum(jnp.sum(stokes, axis=0), time_axis_name)

    return shard_map(local_step, mesh=mesh,
                     in_specs=_P(time_axis_name, None, None),
                     out_specs=_P(None, None))


def sharded_beamform(mesh, ant_axis_name='tp'):
    """Tensor-parallel beamforming GEMM over a sharded antenna axis."""
    from jax import shard_map

    def local_step(w, v):
        return _local_beamform(w, v, ant_axis_name)

    return shard_map(local_step, mesh=mesh,
                     in_specs=(_P(None, ant_axis_name),
                               _P(None, ant_axis_name, None)),
                     out_specs=_P(None, None, None))


def sharded_correlate(mesh, ant_axis_name='tp', time_axis_name='sp'):
    """Cross-correlation (visibilities) with antennas and time sharded."""
    from jax import shard_map

    def local_step(v):
        return _local_correlate(v, ant_axis_name, time_axis_name)

    return shard_map(local_step, mesh=mesh,
                     in_specs=_P(time_axis_name, ant_axis_name, None),
                     out_specs=_P(None, ant_axis_name, None))


def sharded_fir(mesh, coeffs, time_axis_name='sp'):
    """FIR along a time axis sharded across chips (halo via ppermute)."""
    import jax.numpy as jnp
    from jax import shard_map
    coeffs = jnp.asarray(coeffs)

    def local_step(x):
        return _local_fir(x, coeffs, time_axis_name)

    return shard_map(local_step, mesh=mesh,
                     in_specs=_P(time_axis_name),
                     out_specs=_P(time_axis_name))


def sharded_fdmt(mesh, plan, time_axis_name='sp',
                 negative_delays=False, core=None):
    """Time-sharded FDMT over the mesh (long-sequence dedispersion).

    FDMT output column t depends only on input columns
    [t, t + max_delay) for positive delays (the mirror window for
    negative), so each shard fetches a max_delay-wide halo from its
    time neighbor via ppermute — edge shards receive zeros, which is
    exactly the plan's out-of-range semantics — then runs the plan's
    core on its local window.  Input (nchan, T) sharded over
    ``time_axis_name``; output (max_delay, T) sharded the same way,
    bit-compatible with the single-device core.

    ``core`` defaults to the gather core (shape-generic under trace);
    pass a measured winner (ops.fdmt._pick_core) for production.
    Reference capability: bfFdmtExecute (src/fdmt.cu:718) on one GPU —
    the halo exchange is the scale-out this framework adds.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    H = int(plan.max_delay)
    n = int(mesh.shape[time_axis_name])
    if core is None:
        core = plan._core_jax(negative_delays)

    def local_step(x):
        # x: (nchan, T/n)
        if x.shape[1] < H:
            raise ValueError(
                "per-shard time %d < max_delay %d: the halo would "
                "need a non-adjacent neighbor; use fewer shards or "
                "longer gulps" % (x.shape[1], H))
        if negative_delays:
            halo = jax.lax.ppermute(
                x[:, -H:], time_axis_name,
                [(i, i + 1) for i in range(n - 1)])
            xw = jnp.concatenate([halo, x], axis=1)
            return core(xw)[:, H:]
        halo = jax.lax.ppermute(
            x[:, :H], time_axis_name,
            [(i, i - 1) for i in range(1, n)])
        xw = jnp.concatenate([x, halo], axis=1)
        return core(xw)[:, :x.shape[1]]

    return shard_map(local_step, mesh=mesh,
                     in_specs=_P(None, time_axis_name),
                     out_specs=_P(None, time_axis_name))


def spectrometer_step(mesh):
    """The flagship full step, sharded over a ('sp', 'tp') mesh:

    int8 (re,im) voltages (T, A, F, 2)
      -> complexify -> FIR (halo over 'sp')
      -> FFT over F -> beamform (psum over 'tp')
      -> Stokes-power beams -> integrate (psum over 'sp')
      -> correlate (all_gather over 'tp', psum over 'sp')

    Returns (spectra (B, F), visibilities (F, A, A)).  This is the jit
    target of __graft_entry__.dryrun_multichip; it composes the same
    per-shard bodies as the sharded_* wrappers above.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map

    def local_step(volt, weights, coeffs):
        # volt: (T/sp, A/tp, F, 2) int8;  weights: (B, A/tp) complex
        v = volt[..., 0].astype(jnp.float32) + \
            1j * volt[..., 1].astype(jnp.float32)
        vf = _local_fir(v, coeffs, 'sp')
        s = jnp.fft.fft(vf, axis=-1)
        beams = _local_beamform(weights, s, 'tp')
        p = jnp.real(beams) ** 2 + jnp.imag(beams) ** 2
        spectra = jax.lax.psum(jnp.sum(p, axis=0), 'sp')
        vis = _local_correlate(s, 'tp', 'sp')
        return spectra, vis

    return shard_map(
        local_step, mesh=mesh,
        in_specs=(_P('sp', 'tp', None, None), _P(None, 'tp'), _P(None)),
        out_specs=(_P(None, None), _P(None, 'tp', None)))
