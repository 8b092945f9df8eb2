"""FIR filter with decimation and inter-gulp state.

Reference: src/fir.cu:53-416 (multi-tap FIR across ant-pols, carrying
state0/state1 between gulps); python/bifrost/fir.py.

The filter runs along the leading (time) axis.  Coefficients have shape
(ntap,) — shared across channels — or (ntap, *tail_shape) matching the
per-sample tail dims for per-antpol filters (reference semantics).
State (the last ntap-1 input frames) is carried in the plan object, so
streaming gulps are seamless; ``reset_state`` zeroes it
(reference: bfFirResetState).
"""

from __future__ import annotations

import numpy as np

from .common import as_jax
from .fft import _writeback

__all__ = ['Fir']


class Fir(object):
    def __init__(self):
        self._coeffs = None
        self._decim = 1
        self._state = None
        self._fn = {}
        self._mesh = None

    def init(self, coeffs, decim=1, space='tpu', mesh=None):
        """``mesh``: shard the time axis over the mesh's time axis, with
        the inter-shard filter history crossing shard boundaries via a
        ppermute halo exchange (parallel.ops._local_fir_stateful)."""
        import jax.numpy as jnp
        self._coeffs = as_jax(coeffs)
        self._decim = int(decim)
        self._state = None
        self._fn = {}
        self._mesh = mesh
        return self

    def set_coeffs(self, coeffs):
        self._coeffs = as_jax(coeffs)
        self._fn = {}
        return self

    def reset_state(self):
        self._state = None
        return self

    @property
    def ntap(self):
        return self._coeffs.shape[0]

    def _build(self, in_shape, in_dtype):
        import jax
        import jax.numpy as jnp
        coeffs = self._coeffs
        ntap, decim = self.ntap, self._decim

        def fn(x, state):
            # x: (T, ...), state: (ntap-1, ...)
            xp = jnp.concatenate([state, x], axis=0) if ntap > 1 else x
            acc = None
            for t in range(ntap):
                c = coeffs[t]
                sl = xp[ntap - 1 - t: xp.shape[0] - t]
                term = c * sl
                acc = term if acc is None else acc + term
            if decim > 1:
                acc = acc[::decim]
            new_state = xp[-(ntap - 1):] if ntap > 1 else state
            return acc, new_state

        return jax.jit(fn)

    def _mesh_shardable(self, x):
        """Mesh path requires: T divides the time axis; each shard holds
        at least the filter history; per-shard decimation stays aligned."""
        if self._mesh is None:
            return False
        from ..parallel.scope import time_axis_size
        n = time_axis_size(self._mesh)
        local = x.shape[0] // n if x.shape[0] % n == 0 else 0
        return (local > 0 and local >= self.ntap - 1 and
                local % self._decim == 0)

    def _build_sharded(self, in_shape, in_dtype):
        import jax
        from jax.sharding import PartitionSpec as P
        from ..parallel.ops import _local_fir_stateful
        from ..parallel.scope import time_axis_name
        mesh = self._mesh
        tname = time_axis_name(mesh)
        coeffs = self._coeffs
        decim = self._decim
        nd = len(in_shape)
        x_spec = P(*([tname] + [None] * (nd - 1)))
        rep = P(*([None] * nd))

        def local(x, state):
            return _local_fir_stateful(x, coeffs, state, tname, decim)

        return jax.jit(jax.shard_map(
            local, mesh=mesh,
            in_specs=(x_spec, rep), out_specs=(x_spec, rep)))

    def execute(self, idata, odata=None):
        import jax.numpy as jnp
        x = as_jax(idata)
        if not jnp.issubdtype(x.dtype, jnp.inexact):
            x = x.astype(jnp.float32)
        if self._state is None or self._state.shape[1:] != x.shape[1:]:
            self._state = jnp.zeros((max(self.ntap - 1, 1),) + x.shape[1:],
                                    x.dtype)
        sharded = self._mesh_shardable(x)
        key = (x.shape, str(x.dtype), sharded)
        fn = self._fn.get(key)
        if fn is None:
            fn = self._build_sharded(x.shape, x.dtype) if sharded \
                else self._build(x.shape, x.dtype)
            self._fn[key] = fn
        if sharded:
            import jax
            from ..parallel.scope import (shard_gulp, replicated_sharding)
            x = shard_gulp(x, self._mesh, 0)
            state = jax.device_put(
                self._state.astype(x.dtype),
                replicated_sharding(self._mesh))
            y, self._state = fn(x, state)
        else:
            if self._mesh is not None:
                # e.g. a partial final gulp after sharded gulps: the
                # carried state lives on the mesh, this build is
                # single-device — reconcile the device sets.
                from ..parallel.scope import gather_local
                x = gather_local(x)
                self._state = gather_local(self._state)
            y, self._state = fn(x, self._state)
        if odata is not None:
            return _writeback(y, odata)
        return y
