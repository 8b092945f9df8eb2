"""Static and dynamic correctness analysis for bifrost_tpu pipelines
(docs/analysis.md).

Two halves:

- :mod:`bifrost_tpu.analysis.verify` — the **static pipeline
  verifier**: walks a Pipeline's block/ring graph BEFORE ``run()`` and
  emits stable-coded diagnostics (``BF-Exxx`` error / ``BF-Wxxx`` warn
  / ``BF-Ixxx`` info) for misconfigurations that would otherwise
  surface as runtime stalls, gulp-0 exceptions, or silently degraded
  performance.  Exposed as ``Pipeline.validate()``, gated into
  ``Pipeline.run()`` by ``BF_VALIDATE={off,warn,strict}``, and driven
  standalone by ``tools/bf_lint.py``.

- :mod:`bifrost_tpu.analysis.ringcheck` — the **dynamic ring-protocol
  checker** (``BF_RINGCHECK=1``): a shadow state machine hooked into
  the span lifecycle seams shared by BOTH ring cores
  (reserve/commit/acquire/release/poison) that asserts the protocol
  invariants the concurrency layers rely on and raises
  :class:`~bifrost_tpu.analysis.ringcheck.RingProtocolError` with a
  span-history trace on violation.

This package deliberately imports neither :mod:`bifrost_tpu.ring` nor
:mod:`bifrost_tpu.pipeline` at import time — the runtime imports the
checker, and the verifier imports the runtime lazily — so there is no
import cycle and ``BF_RINGCHECK=0`` runs pay a single module-bool test
per seam.
"""

__all__ = ['ringcheck', 'verify']
