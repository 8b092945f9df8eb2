"""Memory helpers: the space lattice plus alloc/copy primitives.

Reference equivalents: python/bifrost/memory.py:37-101 and the native
memory core src/memory.cpp:94-230.  On TPU there is no raw device pointer
to hand out — HBM is owned by the XLA runtime — so raw_malloc returns
host buffers and device 'allocation' happens by constructing jax arrays.
"""

from __future__ import annotations

import os

import numpy as np

from .space import space_accessible, canonical, Space, SPACES  # noqa: F401
from .ndarray import copy_array, memset_array  # noqa: F401

#: Alignment used for host ring allocations; default matches the
#: reference's BF_ALIGNMENT=512 (reference: src/memory.cpp:334-351).
#: Honors the BF_ALIGNMENT environment override the docs have always
#: advertised (the repo-invariant env-var lint, tools/lint_envvars.py,
#: flagged the documented knob as never actually read).
def _alignment_from_env():
    try:
        return max(int(os.environ.get('BF_ALIGNMENT', '512') or 512), 1)
    except ValueError:
        return 512


ALIGNMENT = _alignment_from_env()

#: Depth follows from bytes (docs/transfer.md, "Depth by bytes"): a
#: span of this many bytes or more (one gulp in a ring, one product on
#: its way across the host boundary) is LARGE.  Whatever holds spans in
#: depth, to overlap the work on either side of it, holds large ones
#: two deep and no deeper: a ring its reader sizes (``span_depth`` of
#: the default 3), a block's dispatch-ahead queue, the transfer
#: engine's fills in flight (``INFLIGHT_BYTES``).  The third of a
#: 268 MB gulp costs a ring a sixtieth of a chip's memory and buys
#: slack; the third of a 2.1 GB visibility product costs an eighth.
LARGE_SPAN_BYTES = 1 << 30

#: spans outstanding behind a caller's back (fills in flight, outputs
#: the device has not been waited for) hold at most this many bytes,
#: the newest apart: two spans that are just not large
INFLIGHT_BYTES = 2 * LARGE_SPAN_BYTES


def span_depth(span_nbyte, depth):
    """Spans of ``span_nbyte`` bytes that a buffer asked for ``depth``
    of them holds: ``depth``, or 2 at most once a span is large."""
    return depth if span_nbyte < LARGE_SPAN_BYTES else min(depth, 2)


def raw_malloc(size, space='system'):
    """Allocate ``size`` bytes in a host space, returned as a uint8 numpy
    array aligned to ALIGNMENT (reference: bfMalloc, src/memory.cpp:110)."""
    space = canonical(space)
    if space == 'tpu':
        raise ValueError("Raw device allocation is managed by XLA; "
                         "allocate with bifrost_tpu.empty(space='tpu')")
    buf = np.empty(size + ALIGNMENT, dtype=np.uint8)
    off = (-buf.ctypes.data) % ALIGNMENT
    return buf[off:off + size]


def memcpy(dst, src):
    """Byte copy between host buffers (reference: bfMemcpy,
    src/memory.cpp:163)."""
    dst[...] = src
    return dst


def memset(buf, value=0):
    buf[...] = value
    return buf
