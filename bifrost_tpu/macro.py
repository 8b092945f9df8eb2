"""Macro-gulp execution: K-gulp batched dispatch on the hot path.

``Block.main`` dispatches one XLA program per block per gulp; where
the gap between launches is a large share of a gulp's device time,
that is a dispatch-bound regime.  How large the gap is on the local
v5e has not been measured (ROADMAP S2 decides whether this mechanism
earns its place).

Macro-gulp mode closes that gap at the gulp-loop layer: an eligible
device block acquires/reserves K gulps of ring span in ONE operation,
runs ONE compiled XLA program over the K-gulp batch, and commits all K
gulps at once — turning K dispatch round-trips plus K ring lock cycles
into one.  The reference framework amortizes per-launch cost the same
way one layer down (bifrost batches packet-capture and kernel work per
gulp span); the TPU-DFT work gets its throughput by keeping many
transform steps inside a single XLA program.  This module brings that
discipline to the gulp loop itself.

Two batch-execution shapes, chosen per stage chain
(:func:`chain_batch_mode`):

- **block** — every stage is concat-equivariant along the time axis
  (all built-in stages are), so the composed chain runs directly on
  the stacked K-gulp span.  XLA sees one big program; per-gulp results
  are bit-identical to K=1 because each frame's math is unchanged.
- **sliced** — a stage couples frames across the time axis in a way
  that is not provably concat-safe; the K-gulp span is split into
  per-gulp slices inside one jitted program (``lax.map`` over the
  per-gulp body — one compile, one dispatch, per-gulp semantics
  preserved exactly).

Eligibility (:meth:`bifrost_tpu.pipeline.MultiTransformBlock.
_resolve_macro_batch`) falls back to K=1 — never an error — for host
blocks, unguaranteed readers, dynamic gulp geometry, and
nframe-nonlinear blocks.  Overlapped (FIR/FDMT-history) reads fall
back too UNLESS the block declares ``macro_overlap_safe()`` (the
in-segment halo carry, docs/perf.md): a 'block'-mode stage chain with
a derivable lookahead reads K·G + overlap frames per span — the ghost
history rides at the span head ONCE, interior gulp handoffs happen
inside the program, and the trailing ghost frames go uncommitted.  K=1 is the default and
is byte-identical in behavior to the pre-macro runtime.  Two former
fallbacks are RETIRED (PR 6): multi-reader input rings batch (each
reader's guarantee independently pins its own oldest open span —
both ring cores prove this since the PR 5 multi-open-span fix — so a
K-gulp acquire cannot wedge a peer; sequences that would have been
penalized count on ``macro.fallback.multi_reader_retired``), and
mesh scopes batch (the K-gulp span shards over the mesh time axis
exactly like a single gulp — see docs/parallel.md, "Macro-gulp x
mesh").

Controlled by ``BF_GULP_BATCH`` or the ``gulp_batch`` scope tunable
(``Pipeline(gulp_batch=K)``).  See docs/perf.md ("Macro-gulp
execution") and docs/envvars.md.
"""

from __future__ import annotations

import os

__all__ = ['resolve_gulp_batch', 'retune_gulp_batch',
           'chain_batch_mode', 'build_batched_fn', 'fallback_reason',
           'split_ranges']


def resolve_gulp_batch(scope):
    """Effective macro-gulp batch K for ``scope``: the ``gulp_batch``
    tunable when set anywhere in the scope chain, else the
    BF_GULP_BATCH environment default (1 = off)."""
    k = scope.gulp_batch
    if k is None:
        try:
            k = int(os.environ.get('BF_GULP_BATCH', '1') or 1)
        except ValueError:
            k = 1
    try:
        k = int(k)
    except (TypeError, ValueError):
        return 1
    return max(k, 1)


def retune_gulp_batch(scope, k):
    """Runtime macro-batch retune — the closed-loop auto-tuner's write
    path (docs/autotune.md).  Sets the ``gulp_batch`` scope tunable on
    ``scope`` (normally the Pipeline root, so blocks that pinned their
    own value keep it) and lets the NEXT sequence's
    ``_resolve_macro_batch`` pick it up; sequences already in flight
    keep their active batch — a macro span's geometry cannot change
    mid-sequence.  Returns the clamped value actually set."""
    k = max(int(k), 1)
    scope._gulp_batch = k
    return k


def chain_batch_mode(stages):
    """'block' when every stage declares time-concat equivariance
    (``Stage.batch_safe``), else 'sliced'."""
    if all(getattr(s, 'batch_safe', False) for s in stages):
        return 'block'
    return 'sliced'


def fallback_reason(reason):
    """Record a macro-gulp K=1 fallback on the telemetry counters so an
    operator can see WHY batching did not engage
    (``macro.fallback.<reason>``)."""
    from .telemetry import counters
    counters.inc('macro.fallback.%s' % reason)


def _split_count(nframe, gulp):
    """(full_gulps, remainder_frames) of a macro span."""
    k, r = divmod(int(nframe), int(gulp))
    return k, r


def split_ranges(member_sizes, nsplits):
    """Stage-index ranges of a compiled segment split into
    ``nsplits + 1`` sequential sub-programs (bifrost_tpu.segments,
    the auto-tuner's segment-boundary knob).

    ``member_sizes`` is the per-member stage count of the fused chain
    (split points may only land on member boundaries — a member's own
    stage composition is indivisible).  Members are divided into
    ``nsplits + 1`` contiguous groups as evenly as possible; returns
    ``[(stage_lo, stage_hi), ...]`` half-open ranges into the
    segment's flat stage list.  ``nsplits`` clamps to the available
    boundary count; 0 returns the whole chain as one range."""
    sizes = [int(s) for s in member_sizes]
    nparts = max(min(int(nsplits), len(sizes) - 1), 0) + 1
    # contiguous member groups, balanced like np.array_split
    base, extra = divmod(len(sizes), nparts)
    ranges = []
    m0 = s0 = 0
    for part in range(nparts):
        count = base + (1 if part < extra else 0)
        s1 = s0 + sum(sizes[m0:m0 + count])
        ranges.append((s0, s1))
        m0 += count
        s0 = s1
    return ranges


def build_batched_fn(per_gulp_for_shape, taxis_in, taxis_out,
                     gulp_nframe, part_shapes, mode):
    """Build the ONE-dispatch function over a macro span for a stage
    chain.

    ``per_gulp_for_shape(shape) -> fn`` builds the per-shape chain
    function (the same builder the K=1 path compiles); ``taxis_in`` /
    ``taxis_out`` are the time-axis indices of the chain's input and
    output tensors (they differ when the chain transposes);
    ``gulp_nframe`` the logical gulp G; ``part_shapes`` the static
    shapes of the span's input part(s) (one part normally; several when
    a donated macro span was claimed as multiple exclusively-owned
    chunks); ``mode`` is 'block' or 'sliced'
    (:func:`chain_batch_mode`).

    Returns ``fn(*parts) -> array`` suitable for (donating) jit:

    - parts are concatenated along ``taxis_in`` inside the program
      (free for a single part),
    - 'block': the composed chain runs once on the stacked span (the
      span may carry a lookahead halo — K·G + overlap frames — since
      a concat-equivariant chain computes any span length with the
      same per-frame math; only 'block' chains are halo-carry
      eligible, so 'sliced' never sees an overlapped span),
    - 'sliced': ``lax.map`` applies the per-gulp body to each G-frame
      slice and a statically-shaped tail handles the partial batch at
      sequence end, so per-gulp semantics are preserved exactly.
    """
    import jax.numpy as jnp
    from jax import lax

    nframe = sum(int(s[taxis_in]) for s in part_shapes)
    full_shape = list(part_shapes[0])
    full_shape[taxis_in] = nframe

    if mode == 'block':
        body = per_gulp_for_shape(tuple(full_shape))

        def fn(*parts):
            x = parts[0] if len(parts) == 1 else \
                jnp.concatenate(parts, axis=taxis_in)
            return body(x)
        return fn

    k, rem = _split_count(nframe, gulp_nframe)
    gulp_shape = list(full_shape)
    gulp_shape[taxis_in] = int(gulp_nframe)
    body = per_gulp_for_shape(tuple(gulp_shape)) if k else None
    tail_shape = list(full_shape)
    tail_shape[taxis_in] = rem
    tail = per_gulp_for_shape(tuple(tail_shape)) if rem else None
    G = int(gulp_nframe)

    def fn(*parts):
        x = parts[0] if len(parts) == 1 else \
            jnp.concatenate(parts, axis=taxis_in)
        outs = []
        if k:
            def per(i):
                return body(lax.dynamic_slice_in_dim(x, i * G, G,
                                                     axis=taxis_in))
            ys = lax.map(per, jnp.arange(k))
            # (k, ..., G_out, ...) -> (..., k * G_out, ...)
            ys = jnp.moveaxis(ys, 0, taxis_out)
            merged = (ys.shape[:taxis_out] +
                      (ys.shape[taxis_out] * ys.shape[taxis_out + 1],) +
                      ys.shape[taxis_out + 2:])
            outs.append(ys.reshape(merged))
        if rem:
            idx = [slice(None)] * len(full_shape)
            idx[taxis_in] = slice(k * G, nframe)
            outs.append(tail(x[tuple(idx)]))
        if len(outs) == 1:
            return outs[0]
        return jnp.concatenate(outs, axis=taxis_out)
    return fn
