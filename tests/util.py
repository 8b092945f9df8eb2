"""In-test source/sink blocks (pattern from reference:
test/test_pipeline.py:43-113 CallbackBlock)."""

from __future__ import annotations

import socket

import numpy as np

import bifrost_tpu as bf
from bifrost_tpu.pipeline import SourceBlock, SinkBlock, TransformBlock


class _NumpyReader(object):
    def __init__(self, arrays):
        self.arrays = list(arrays)
        self.pos = 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def read(self, nframe):
        if self.pos >= len(self.arrays):
            return None
        out = self.arrays[self.pos]
        self.pos += 1
        return out


class NumpySourceBlock(SourceBlock):
    """Source emitting a list of numpy gulps with a given header tensor."""

    def __init__(self, gulps, header, gulp_nframe, space='system',
                 **kwargs):
        super(NumpySourceBlock, self).__init__(['numpy'], gulp_nframe,
                                               space=space, **kwargs)
        self._gulps = gulps
        self._header = header

    def create_reader(self, sourcename):
        return _NumpyReader(self._gulps)

    def static_oheaders(self):
        # static-verification protocol (bifrost_tpu.analysis.verify):
        # the header is fixed at construction, so advertise it
        return [dict(self._header)]

    def on_sequence(self, reader, sourcename):
        return [dict(self._header)]

    def on_data(self, reader, ospans):
        arr = reader.read(self.gulp_nframe)
        if arr is None:
            return [0]
        ospan = ospans[0]
        nframe = min(arr.shape[0], ospan.nframe)
        data = ospan.data.as_numpy()
        data[:nframe] = arr[:nframe]
        return [nframe]


class CallbackSinkBlock(SinkBlock):
    """Sink invoking callbacks on each header/gulp."""

    def __init__(self, iring, seq_callback=None, data_callback=None,
                 **kwargs):
        super(CallbackSinkBlock, self).__init__(iring, **kwargs)
        self._seq_cb = seq_callback
        self._data_cb = data_callback

    def on_sequence(self, iseq):
        if self._seq_cb is not None:
            self._seq_cb(iseq.header)

    def on_data(self, ispan):
        if self._data_cb is not None:
            if ispan.ring.space == 'tpu':
                from bifrost_tpu.xfer import to_host
                self._data_cb(to_host(ispan.data))
            else:
                self._data_cb(np.array(ispan.data.as_numpy(), copy=True))


class GatherSink(CallbackSinkBlock):
    """Sink that concatenates all received gulps for assertions."""

    def __init__(self, iring, **kwargs):
        self.headers = []
        self.gulps = []
        super(GatherSink, self).__init__(
            iring,
            seq_callback=self.headers.append,
            data_callback=self.gulps.append, **kwargs)

    def result(self):
        return np.concatenate(self.gulps, axis=0) if self.gulps else None


def simple_header(shape, dtype, labels=None, name='test', gulp_nframe=None):
    """Build a minimal sequence header; shape uses -1 for the time axis."""
    n = len(shape)
    if labels is None:
        labels = ['time'] + ['dim%d' % i for i in range(1, n)]
    hdr = {
        'name': name,
        'time_tag': 0,
        '_tensor': {
            'shape': list(shape),
            'dtype': str(dtype),
            'labels': list(labels),
            'scales': [[0, 1]] * n,
            'units': [None] * n,
        },
    }
    if gulp_nframe is not None:
        hdr['gulp_nframe'] = gulp_nframe
    return hdr


def run_pipeline(pipeline=None):
    p = pipeline or bf.get_default_pipeline()
    p.run()
    return p


def _bound_socket(port=0):
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    try:
        s.bind(('127.0.0.1', port))
    except OSError:
        s.close()
        raise
    return s


def free_ports(n, exclude=()):
    """``n`` distinct free loopback ports, reserved briefly."""
    socks = []
    try:
        while len(socks) < n:
            s = _bound_socket()
            if s.getsockname()[1] in exclude:
                s.close()
            else:
                socks.append(s)
        return [s.getsockname()[1] for s in socks]
    finally:
        for s in socks:
            s.close()


def port_block(n, tries=64):
    """Base of ``n`` CONSECUTIVE free ports: fan endpoints derive
    ``port + i``, so the whole derived range is probed."""
    for _ in range(tries):
        socks = [_bound_socket()]
        try:
            base = socks[0].getsockname()[1]
            for i in range(1, n):
                socks.append(_bound_socket(base + i))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError('no block of %d consecutive free ports' % n)
