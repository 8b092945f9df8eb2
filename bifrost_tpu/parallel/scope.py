"""Pipeline <-> mesh glue: how ``BlockScope(mesh=...)`` becomes sharded
execution inside blocks.

The reference's analogue is per-block device placement (`gpu=N` ->
set_device on the block thread, reference: python/bifrost/pipeline.py:365-366).
On TPU a block scales *out* instead: its jitted gulp function runs over a
``jax.sharding.Mesh``, with the gulp's frame (time) axis sharded across
the mesh's time axis.  Two integration styles, both driven from here:

- **GSPMD** (generic stage chains — FusedBlock): ``jax.jit`` with
  ``in_shardings`` on the frame axis; XLA partitions the whole fused
  chain and inserts any collectives it needs.  Right for arbitrary stage
  compositions where the collective pattern is not known a priori.
- **shard_map** (ops with a known collective pattern — correlate's
  time-psum, FIR's halo exchange): explicit per-shard bodies from
  :mod:`bifrost_tpu.parallel.ops`.

Axis-name conventions: the *time* axis of a mesh is ``'sp'`` if present,
else the first axis; the *station* axis is ``'tp'`` if present.
"""

from __future__ import annotations

import os

__all__ = ['time_axis_name', 'station_axis_name', 'time_axis_size',
           'time_sharding', 'replicated_sharding', 'shardable_nframe',
           'shard_gulp', 'gather_local', 'sharding_descriptor',
           'descriptor_matches', 'meshes_equivalent',
           'check_descriptor', 'frame_local_plan',
           'mesh_h2d_enabled', 'hlo_stats_enabled', 'collective_counts',
           'record_collectives']


def time_axis_name(mesh):
    """The mesh axis that gulp frame/time axes shard over."""
    return 'sp' if 'sp' in mesh.axis_names else mesh.axis_names[0]


def station_axis_name(mesh):
    """The mesh axis for antenna/station sharding, or None."""
    return 'tp' if 'tp' in mesh.axis_names else None


def time_axis_size(mesh):
    return mesh.shape[time_axis_name(mesh)]


def time_sharding(mesh, ndim, taxis):
    """NamedSharding placing axis ``taxis`` of an ndim-array over the
    mesh's time axis (all other axes replicated)."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = [None] * ndim
    spec[taxis] = time_axis_name(mesh)
    return NamedSharding(mesh, PartitionSpec(*spec))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec())


def shardable_nframe(mesh, nframe):
    """Whether a gulp of ``nframe`` frames divides over the time axis."""
    return nframe % time_axis_size(mesh) == 0


def shard_gulp(x, mesh, taxis):
    """Lay a gulp array out over the mesh (frame axis sharded).  A no-op
    when the frame axis does not divide the mesh, or when the array is
    already in the target layout.  An actual relayout is counted on the
    ``mesh.reshards`` telemetry counter — in a mesh-resident pipeline
    (sharded H2D placement + ring-resident shardings) the steady state
    is ZERO hits here; a nonzero rate means a block is committing spans
    in a layout its consumer has to move."""
    import jax
    if x.shape[taxis] % time_axis_size(mesh):
        return x
    sharding = time_sharding(mesh, x.ndim, taxis)
    if getattr(x, 'sharding', None) == sharding:
        return x
    from ..telemetry import counters
    counters.inc('mesh.reshards')
    counters.inc('mesh.reshard_bytes', int(getattr(x, 'nbytes', 0) or 0))
    return jax.device_put(x, sharding)


def sharding_descriptor(mesh, taxis):
    """JSON-able record of a ring-resident gulp sharding, written into
    sequence headers under ``_sharding`` so downstream blocks (and the
    monitor tools) can see HOW spans of this sequence are laid out
    without holding the live Mesh object: the mesh axis dict, the
    sharded tensor axis, and the axis name the frame axis shards over."""
    return {
        'mesh_axes': {str(n): int(s)
                      for n, s in zip(mesh.axis_names,
                                      mesh.devices.shape)},
        'taxis': int(taxis),
        'axis': time_axis_name(mesh),
        'nshards': int(time_axis_size(mesh)),
    }


def meshes_equivalent(mesh_a, mesh_b):
    """Whether two mesh scopes produce interchangeable ring-resident
    gulp layouts: same axis-name/size table and the same time axis, so
    a span committed under one is consumed by the other with zero
    reshards.  ``None`` vs a real mesh is never equivalent (one side
    commits single-device spans).  The static pipeline verifier
    (bifrost_tpu.analysis.verify) uses this to predict
    ``mesh.reshards > 0`` at submit time."""
    if mesh_a is None or mesh_b is None:
        return mesh_a is mesh_b
    if mesh_a is mesh_b:
        return True
    try:
        axes_a = {str(n): int(s) for n, s in zip(mesh_a.axis_names,
                                                 mesh_a.devices.shape)}
        axes_b = {str(n): int(s) for n, s in zip(mesh_b.axis_names,
                                                 mesh_b.devices.shape)}
        return (axes_a == axes_b and
                time_axis_name(mesh_a) == time_axis_name(mesh_b) and
                mesh_a.devices.tolist() == mesh_b.devices.tolist())
    except Exception:
        return False


def descriptor_matches(desc, mesh, taxis):
    """Whether a header's ``_sharding`` descriptor describes the layout
    ``time_sharding(mesh, ·, taxis)`` would produce on THIS mesh —
    consumer blocks use this to flag a producer advertising a layout
    their own scope's mesh would have to move (``mesh.layout_mismatch``
    telemetry; the steady state of a mesh-resident chain is every
    descriptor matching)."""
    if not isinstance(desc, dict) or mesh is None:
        return False
    want = sharding_descriptor(mesh, taxis)
    return all(desc.get(k) == v for k, v in want.items())


def check_descriptor(ihdr, mesh, taxis):
    """Count a producer/consumer layout disagreement: the input
    header's ``_sharding`` descriptor (when the producer wrote one)
    must describe the layout this consumer's mesh scope expects, else
    every gulp of the sequence will pay a relayout — surface it once
    per sequence on ``mesh.layout_mismatch`` instead of only as a
    per-gulp ``mesh.reshards`` drip."""
    desc = ihdr.get('_sharding') if isinstance(ihdr, dict) else None
    if desc is None or mesh is None:
        return
    if not descriptor_matches(desc, mesh, taxis):
        from ..telemetry import counters
        counters.inc('mesh.layout_mismatch')


def frame_local_plan(mesh, build_local, shape, dtype, taxis_in,
                     taxis_out, donate_argnums=()):
    """jit(shard_map(local_body)) over the mesh time axis for a
    TIME-CONCAT-EQUIVARIANT gulp function: each device runs
    ``build_local(per_shard_shape)`` on its contiguous frame block, so
    the compiled program contains NO collectives by construction — the
    strongest form of the zero-reshard property (GSPMD with
    in/out_shardings merely *asks* the partitioner not to move data;
    this shape makes movement inexpressible).  Equivariance is exactly
    the ``Stage.batch_safe`` contract macro-gulp execution already
    relies on, so eligibility is shared, not re-derived.

    ``in_shardings``/``out_shardings`` pin the ring-resident layout:
    committed input chunks arrive pre-sharded (sharded H2D / upstream
    out_shardings) and the output commits sharded for the next block.

    Returns ``(jitted, in_sharding, out_sharding)`` or None when the
    frame axis does not divide the mesh or the local build fails
    (caller falls back to a GSPMD plan)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    nsh = time_axis_size(mesh)
    if shape[taxis_in] % nsh:
        return None
    local = list(shape)
    local[taxis_in] //= nsh
    aname = time_axis_name(mesh)
    try:
        body = build_local(tuple(local))
        out_l = jax.eval_shape(body,
                               jax.ShapeDtypeStruct(tuple(local), dtype))
        if taxis_out >= out_l.ndim:
            return None
        spec_in = PartitionSpec(*[aname if i == taxis_in else None
                                  for i in range(len(shape))])
        spec_out = PartitionSpec(*[aname if i == taxis_out else None
                                   for i in range(out_l.ndim)])
        # bodies may carry no varying-mesh-axis metadata (pallas
        # kernels), so the check is off
        sharded = jax.shard_map(body, mesh=mesh, in_specs=spec_in,
                                out_specs=spec_out, check_vma=False)
        in_sh = NamedSharding(mesh, spec_in)
        out_sh = NamedSharding(mesh, spec_out)
        from ..ops.common import donating_jit
        jitted = donating_jit(sharded, donate_argnums=donate_argnums,
                              in_shardings=in_sh, out_shardings=out_sh)
    except Exception as e:
        # the caller degrades to GSPMD — which on some partitioners
        # (CPU) re-introduces the collectives this path exists to
        # preclude; make that degradation visible like every other
        # fallback (the divisibility early-return above is an expected
        # geometry case and is not counted)
        from ..ops import mprobe
        from ..telemetry import counters
        mprobe.refused('mesh', 'frame_local', e)
        counters.inc('mesh.frame_local_fallback')
        return None
    return jitted, in_sh, out_sh


def mesh_h2d_enabled():
    """Sharded H2D placement (per-shard staging +
    jax.make_array_from_single_device_arrays in xfer.to_device) —
    BF_MESH_H2D=0 falls back to whole-array device_put onto the
    sharding (one extra on-device scatter)."""
    return os.environ.get('BF_MESH_H2D', '1') != '0'


def hlo_stats_enabled():
    """Whether mesh plan builds should ALSO compile an analysis copy and
    count the collectives XLA inserted (``mesh.collectives.<kind>``
    counters).  Off by default — it doubles compile time per plan —
    BF_MESH_HLO_STATS=1 enables (tests use it to assert the
    zero-reshard property)."""
    return os.environ.get('BF_MESH_HLO_STATS', '0') == '1'


#: HLO op substrings -> counter key (the genuine collectives a sharded
#: plan may legitimately contain, vs the reshard smells all-gather /
#: all-to-all between chained blocks)
_COLLECTIVE_KINDS = (('all-gather', 'all_gather'),
                     ('all-reduce', 'all_reduce'),
                     ('reduce-scatter', 'reduce_scatter'),
                     ('all-to-all', 'all_to_all'),
                     ('collective-permute', 'collective_permute'))


def collective_counts(hlo_text):
    """Occurrences of each collective op family in compiled HLO text
    (instruction positions only: ``<op>`` at the start of an
    instruction name like ``all-gather.1 = ...``).  Async HLO pairs
    (``all-gather-start`` / ``all-gather-done``) count ONCE — the
    ``-done`` half is the same collective's completion, and counting
    both would double every collective on backends that emit async
    pairs (real TPU) versus the sync-HLO CPU baseline."""
    out = {}
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        # instruction definitions look like '%all-gather.3 = ',
        # 'all-gather.3 = ', or 'ROOT %all-gather = ' when the
        # collective is the computation root; fusion parameter
        # mentions don't count
        if ls.startswith('ROOT '):
            ls = ls[5:]
        if ls.startswith('%'):
            ls = ls[1:]
        for needle, key in _COLLECTIVE_KINDS:
            if ls.startswith(needle) and \
                    not ls[len(needle):].startswith('-done'):
                out[key] = out.get(key, 0) + 1
                break
    return out


def record_collectives(jitted, args, label):
    """Compile an analysis copy of ``jitted`` at ``args`` (ShapeDtype
    structs with shardings) and record the collectives XLA inserted on
    the ``mesh.collectives.<kind>`` counters; returns the count dict.
    Only called when :func:`hlo_stats_enabled`.  Best-effort: analysis
    failure never breaks the plan build."""
    from ..telemetry import counters
    try:
        txt = jitted.lower(*args).compile().as_text()
    except Exception:
        return None
    counts = collective_counts(txt)
    for kind, n in counts.items():
        counters.inc('mesh.collectives.%s' % kind, n)
    counters.inc('mesh.plans_analyzed')
    if not counts:
        counters.inc('mesh.plans_collective_free')
    return counts


def gather_local(x):
    """Bring a (possibly mesh-committed) array back to this thread's
    single device.  Blocks need this when they fall back from the
    sharded to the unsharded build mid-sequence (e.g. a partial final
    gulp) while carrying state computed on the mesh — mixing committed
    device sets in one jit call is an error."""
    import jax
    if isinstance(x, jax.Array) and \
            len(getattr(x, 'sharding').device_set) > 1:
        from ..device import get_device
        return jax.device_put(x, get_device())
    return x
