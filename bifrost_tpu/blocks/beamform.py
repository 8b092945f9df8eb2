"""Coherent beamformer block (reference: the bfLinAlgMatMul beamform
GEMM, src/linalg.cu:877-904, driven per-gulp; recipe papers
arXiv:2505.03269 / arXiv:1412.4907).

The math/metadata lives in stages.BeamformStage, so the same code runs
standalone here, fused into a chain (``bf.blocks.fused([BeamformStage,
DetectStage, ReduceStage])`` — where the whole-chain Pallas
substitution applies, stages.match_beamformer), macro-gulp batched, or
mesh-sharded along the frame axis via the _StageBlock machinery
(frame-local shard_map when equivariant — which beamforming is —
GSPMD otherwise; docs/parallel.md)."""

from __future__ import annotations

from ..dtype import DataType
from ..stages import BeamformStage
from .fft import _StageBlock

__all__ = ['BeamformBlock', 'beamform']


class BeamformBlock(_StageBlock):
    """Beamform a ['time', 'freq', 'station'[, 'pol']] voltage stream
    against a fixed weight set: one for every channel, or with
    ``(F, P, B, S)`` weights a set per channel (stages.BeamformStage).
    The output is the complex beams, so at a deployment's shape, where
    they would not fit the chip, the fused chain is the form to use
    (stages.match_beamformer).  ``accuracy`` declares the class lossy
    candidates must stay inside to race ('f32' | 'bf16' | 'int8' —
    ops.beamform docstring); ``impl`` / ``BF_BEAM_IMPL`` force one."""

    def __init__(self, iring, weights, accuracy='f32', impl=None,
                 *args, **kwargs):
        super(BeamformBlock, self).__init__(
            iring, BeamformStage(weights, accuracy=accuracy,
                                 impl=impl), *args, **kwargs)

    @property
    def engine(self):
        return self._stage.engine

    def on_sequence(self, iseq):
        ohdr = super(BeamformBlock, self).on_sequence(iseq)
        self._prewarm_engine(iseq.header)
        return ohdr

    def _prewarm_engine(self, ihdr):
        """Gate + race the engine's candidates at the shape on_data's
        jit trace will present (per-shard under a mesh), so the winner
        comes from the cache instead of the class default — probe cost
        lands at sequence start, never as first-gulp latency (the
        CorrelateBlock._prewarm_xcorr policy).  Best-effort: the traced
        default is always correct."""
        try:
            t = ihdr.get('_tensor', {})
            gulp = self.gulp_nframe or ihdr.get('gulp_nframe')
            if not gulp:
                return
            stage = self._stage
            shape = t['shape']
            nfreq = shape[1]
            dt = DataType(t['dtype'])
            int_input = dt.kind == 'ci' and dt.nbits == 8
            t_eff = int(gulp)
            # macro-gulp: the steady-state trace sees K time-concat
            # gulps in ONE call (block batch mode — BeamformStage is
            # batch_safe), so the winner must be raced at the K-gulp
            # shape too or the traced lookup key-misses and silently
            # falls back to the class default
            from ..macro import resolve_gulp_batch
            try:
                k = resolve_gulp_batch(self)
            except Exception:
                k = 1
            shapes = [t_eff] if k <= 1 else [t_eff, t_eff * k]
            npol = stage.npol if stage.mode == 'perpol' else 1
            for t_shape in shapes:
                if self.mesh is not None:
                    from ..parallel.scope import (shardable_nframe,
                                                  time_axis_size)
                    if shardable_nframe(self.mesh, t_shape):
                        t_shape //= time_axis_size(self.mesh)
                stage.engine.prewarm(t_shape, nfreq, npol=npol,
                                     int_input=int_input)
            # GEMM-class ops accounting (like_top's GOP/s column,
            # docs/perf.md): real ops per logical gulp of this
            # sequence, published via the gemm_gops_per_s perf key
            self._gemm_ops = stage.engine.ops_per_frame(
                nfreq, npol) * int(gulp)
        except Exception as e:
            # probing is best-effort — the traced default works — but
            # a refusal is never dropped without a word
            from ..ops import mprobe
            mprobe.refused('beamform', 'prewarm', e)


def beamform(iring, weights, accuracy='f32', impl=None, *args,
             **kwargs):
    """Block: coherent beamform against ``weights`` through the
    quantized beamformer engine (ops.beamform; candidates raced and
    accuracy-gated per the declared class)."""
    return BeamformBlock(iring, weights, accuracy, impl, *args,
                         **kwargs)
