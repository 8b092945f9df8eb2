"""Space↔space mover — the H2D/D2H block (reference:
python/bifrost/blocks/copy.py:45-71).

Conversion between host storage and the device representation is defined
in :mod:`bifrost_tpu.devrep` (bit-exact round trips; how complex data
crosses the host boundary: xfer.py).

Both directions ride the async transfer engine (bifrost_tpu.xfer):

- host→device gulps are shipped with a non-blocking device_put
  (devrep → xfer): to one device on a copying backend from the input
  ring's own span, which the engine keeps open (a second open span of
  this block's reader) until the transfer has consumed it, and which
  this block has it release before its reader leaves the sequence;
  staged through the engine's reusable buffer ring otherwise
  (docs/transfer.md, "From the ring span");
- device→host gulps are committed as *deferred fills*
  (xfer.HostFill): the span publishes immediately, the D2H readback
  runs in flight, and readers of the output ring materialize the bytes
  only when they first touch them — the writer thread never pays the
  per-gulp hard sync the old ``np.asarray`` path did.

``sync_strict=True`` (scope tunable) or BF_SYNC_STRICT=1 restores the
fully synchronous behavior: every D2H completes before the span
commits (the strict-mode completion bound).
"""

from __future__ import annotations

from copy import deepcopy

from ..pipeline import TransformBlock
from ..ndarray import copy_array
from ..devrep import to_device_rep, from_device_rep, device_rep_zeros

__all__ = ['CopyBlock', 'copy',
           'to_device_rep', 'from_device_rep', 'device_rep_zeros']


class CopyBlock(TransformBlock):
    """Copy data, possibly between spaces
    (reference: blocks/copy.py:36-58)."""

    def __init__(self, iring, space=None, *args, **kwargs):
        super(CopyBlock, self).__init__(iring, *args, **kwargs)
        if space is None:
            space = self.irings[0].space
        self.orings = [self.create_ring(space=space)]

    def define_valid_input_spaces(self):
        return 'any'

    def macro_gulp_safe(self):
        """Macro-gulp eligible on the device paths: an H2D copy over a
        K-gulp span stages K gulps with ONE engine call (one aligned
        staging copy + one device_put instead of K), a D2H copy drains
        ONE deferred fill per K gulps, and a device-device copy
        republishes one chunk.  Host-only copies gain nothing from
        batching and keep per-gulp granularity."""
        return 'tpu' in (self.irings[0].space, self.orings[0].space)

    def verify_header(self, ihdr):
        """Static-verification protocol (bifrost_tpu.analysis.verify):
        a copy preserves the stream contract (the runtime on_sequence
        additionally rewrites the ``_sharding`` advertisement, which
        the static walk does not model)."""
        ohdr = deepcopy(ihdr)
        ohdr.pop('_sharding', None)
        return ohdr

    def on_sequence(self, iseq):
        ohdr = deepcopy(iseq.header)
        self._h2d_taxis = None
        if self.orings[0].space != 'tpu':
            # host rings have no device layout: a D2H copy gathers
            ohdr.pop('_sharding', None)
        if self.mesh is not None and self.orings[0].space == 'tpu' \
                and self.irings[0].space != 'tpu':
            # mesh-resident placement: this mover will commit spans
            # sharded over the scope mesh's time axis; advertise the
            # ring-resident layout so downstream blocks jit with
            # matching in_shardings (zero inter-block reshards) and
            # monitors can see it (docs/parallel.md)
            from ..parallel.scope import sharding_descriptor
            try:
                taxis = ohdr['_tensor']['shape'].index(-1)
            except (KeyError, ValueError):
                taxis = None
            if taxis is not None:
                self._h2d_taxis = taxis
                ohdr['_sharding'] = sharding_descriptor(self.mesh, taxis)
        return ohdr

    def _h2d_sharding(self, ispan):
        """NamedSharding for this gulp's DEVICE-REP array (frame axis
        over the mesh time axis), or None when no mesh is scoped or the
        gulp's frame count does not divide the shards (the partial tail
        at sequence end lands single-device; consumers fall back the
        same way)."""
        if self._h2d_taxis is None:
            return None
        from ..parallel.scope import time_sharding, time_axis_size
        if ispan.nframe % time_axis_size(self.mesh):
            return None
        from ..dtype import DataType
        ndim = len(ispan.shape)
        if DataType(ispan.dtype).kind == 'ci':
            ndim += 1        # device rep grows a trailing (re,im) axis
        return time_sharding(self.mesh, ndim, self._h2d_taxis)

    def _process_sequence(self, orings, iseqs):
        """The spans of the input ring that H2D transfers still read
        (``xfer._Hold``) are this reader's: they are waited for and
        released before it moves on to another sequence or closes,
        however the sequence ends (its end, a shutdown, a failure)."""
        try:
            return super(CopyBlock, self)._process_sequence(orings, iseqs)
        finally:
            from .. import xfer
            xfer.engine().release_held(iseqs[0])

    def _d2h_strict(self):
        """Synchronous D2H required?  Scope sync_strict wins; else the
        engine's global async switch (BF_SYNC_STRICT / BF_XFER_ASYNC)."""
        from .. import xfer
        if self.sync_strict is not None:
            return bool(self.sync_strict)
        return not xfer.async_enabled()

    def on_data(self, ispan, ospan):
        ispace = ispan.ring.space
        ospace = ospan.ring.space
        if ospace == 'tpu' and ispace != 'tpu':
            buf = ispan.data.as_numpy()
            # engine-created device array: the committed chunk is
            # exclusively this ring's (donation-eligible downstream);
            # a ci8 gulp bound for one device crosses as its words,
            # and from the span's own memory where the engine can hold
            # the span open meanwhile
            ospan.set(to_device_rep(buf, ispan.dtype,
                                    sharding=self._h2d_sharding(ispan),
                                    span=ispan),
                      owned=True)
        elif ispace == 'tpu' and ospace != 'tpu':
            out = ospan.data.as_numpy()
            # a complex product its writer left as two real planes
            # is cut from them (devrep.ComplexPlanes); the words of a
            # ci8 gulp are the host's bytes, and cross as they are
            # (devrep.ComplexWords)
            src = ispan.planes
            if src is None:
                src = ispan.words
            if src is None:
                src = ispan.data
            if self._d2h_strict():
                from_device_rep(src, ospan.dtype, out)
            else:
                # non-blocking: commit the span now, let the engine's
                # bounded queue + the reader materialize the bytes
                from .. import xfer
                fill = xfer.engine().host_fill(src, ospan.dtype, out)
                ospan.set_fill(fill)
        elif ispace == 'tpu' and ospace == 'tpu':
            ospan.set(ispan.data)
        else:
            copy_array(ospan.data, ispan.data)


def copy(iring, space=None, *args, **kwargs):
    """Block: copy data, possibly to another space."""
    return CopyBlock(iring, space, *args, **kwargs)
