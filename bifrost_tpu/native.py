"""ctypes bindings for the native ring runtime (native/ring.cpp).

The reference generates its Python bindings from the C headers with
ctypesgen (reference: python/Makefile.in:23-30); here the ABI is small
enough to declare by hand.  The library is built on demand with
``make -C native`` the first time it's needed; a build or load that
fails warns once with the cause (``make``'s stderr) before host rings
take the pure-Python core.

Set ``BF_NO_NATIVE=1`` to force the pure-Python ring core.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings

__all__ = ['load', 'available', 'BFT_OK', 'BFT_END_OF_DATA',
           'BFT_WOULD_BLOCK', 'NativeError']

BFT_OK = 0
BFT_END_OF_DATA = 1
BFT_WOULD_BLOCK = 2

_lock = threading.Lock()
_lib = None
_tried = False


class NativeError(RuntimeError):
    pass


def _repo_root():
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lib_path():
    return os.path.join(_repo_root(), 'native', 'build',
                        'libbifrost_tpu.so')


def _declare(lib):
    c = ctypes
    P = c.POINTER
    ll = c.c_longlong
    sigs = {
        'bft_ring_create': ([P(c.c_void_p), c.c_char_p], c.c_int),
        'bft_ring_destroy': ([c.c_void_p], c.c_int),
        'bft_ring_resize': ([c.c_void_p, ll, ll, ll], c.c_int),
        'bft_ring_request_resize': ([c.c_void_p, ll, ll, ll,
                                     P(c.c_int)], c.c_int),
        'bft_ring_resize_pending': ([c.c_void_p, P(c.c_int)], c.c_int),
        'bft_ring_resize_hold': ([c.c_void_p, c.c_int], c.c_int),
        'bft_ring_set_core': ([c.c_void_p, c.c_int], c.c_int),
        'bft_ring_geometry': ([c.c_void_p, P(P(c.c_ubyte)), P(ll), P(ll),
                               P(ll)], c.c_int),
        'bft_ring_begin_writing': ([c.c_void_p], c.c_int),
        'bft_ring_end_writing': ([c.c_void_p], c.c_int),
        'bft_ring_begin_sequence': ([c.c_void_p, c.c_char_p, ll,
                                     c.c_char_p, ll, ll,
                                     P(c.c_void_p)], c.c_int),
        'bft_ring_end_sequence': ([c.c_void_p, c.c_void_p], c.c_int),
        'bft_seq_info': ([c.c_void_p, P(c.c_char_p), P(ll),
                          P(c.c_char_p), P(ll), P(ll), P(ll)], c.c_int),
        'bft_seq_end_offset': ([c.c_void_p, P(ll)], c.c_int),
        'bft_ring_reserve': ([c.c_void_p, ll, c.c_int, P(ll), P(ll)],
                             c.c_int),
        'bft_ring_reserve_shed': ([c.c_void_p, ll, ll, P(ll), P(ll),
                                   P(ll)], c.c_int),
        'bft_ring_commit': ([c.c_void_p, ll, ll], c.c_int),
        'bft_capture_create': ([P(c.c_void_p), c.c_int, c.c_int,
                                c.c_void_p, c.c_int, c.c_int, c.c_int,
                                c.c_int, c.c_int], c.c_int),
        'bft_capture_set_header_callback': ([c.c_void_p, c.c_void_p,
                                             c.c_void_p], c.c_int),
        'bft_capture_set_timeout_ms': ([c.c_void_p, c.c_int], c.c_int),
        'bft_capture_set_decimation': ([c.c_void_p, c.c_int], c.c_int),
        'bft_capture_recv': ([c.c_void_p, P(c.c_int)], c.c_int),
        'bft_capture_flush': ([c.c_void_p], c.c_int),
        'bft_capture_end': ([c.c_void_p], c.c_int),
        'bft_capture_stats': ([c.c_void_p, P(ll), P(ll), P(ll), P(ll)],
                              c.c_int),
        'bft_capture_src_ngood': ([c.c_void_p, P(ll), c.c_int], c.c_int),
        'bft_transmit_create': ([P(c.c_void_p), c.c_int, c.c_int],
                                c.c_int),
        'bft_transmit_set_rate': ([c.c_void_p, ll], c.c_int),
        'bft_transmit_set_nbeam': ([c.c_void_p, c.c_int], c.c_int),
        'bft_transmit_set_vdif': ([c.c_void_p, c.c_int, c.c_int,
                                   c.c_int, c.c_int, c.c_int, c.c_int,
                                   c.c_int], c.c_int),
        'bft_transmit_send': ([c.c_void_p, ll, ll, c.c_int, c.c_int,
                               c.c_int, c.c_int, c.c_int, c.c_int,
                               c.c_int, c.c_int, ll,
                               P(c.c_ubyte), c.c_int, c.c_int,
                               c.c_int, P(ll)], c.c_int),
        'bft_transmit_destroy': ([c.c_void_p], c.c_int),
        'bft_selftest': ([], c.c_int),
        'bft_capture_destroy': ([c.c_void_p], c.c_int),
        'bft_reader_create': ([c.c_void_p, c.c_int, P(ll)], c.c_int),
        'bft_reader_destroy': ([c.c_void_p, ll], c.c_int),
        'bft_reader_set_guarantee': ([c.c_void_p, ll, ll, c.c_int],
                                     c.c_int),
        'bft_ring_open_sequence': ([c.c_void_p, c.c_int, c.c_char_p, ll,
                                    P(c.c_void_p)], c.c_int),
        'bft_seq_next': ([c.c_void_p, c.c_void_p, P(c.c_void_p)], c.c_int),
        'bft_reader_acquire': ([c.c_void_p, ll, c.c_void_p, ll, ll, ll,
                                P(ll), P(ll)], c.c_int),
        'bft_reader_release': ([c.c_void_p, ll, ll], c.c_int),
        'bft_ring_overwritten_in': ([c.c_void_p, ll, ll, P(ll)], c.c_int),
        'bft_ring_tail_head': ([c.c_void_p, P(ll), P(ll)], c.c_int),
        'bft_version': ([], c.c_int),
        # util.cpp: affinity / aligned host memory / ProcLog writer
        'bft_affinity_set_core': ([c.c_int], c.c_int),
        'bft_affinity_get_core': ([P(c.c_int)], c.c_int),
        'bft_malloc': ([P(c.c_void_p), ll], c.c_int),
        'bft_free': ([c.c_void_p], c.c_int),
        'bft_memcpy': ([c.c_void_p, c.c_void_p, ll], c.c_int),
        'bft_memcpy2d': ([c.c_void_p, ll, c.c_void_p, ll, ll, ll],
                         c.c_int),
        'bft_memset': ([c.c_void_p, c.c_int, ll], c.c_int),
        'bft_memset2d': ([c.c_void_p, ll, c.c_int, ll, ll], c.c_int),
        'bft_proclog_set_base': ([c.c_char_p], c.c_int),
        'bft_proclog_update': ([c.c_char_p, c.c_char_p, c.c_char_p],
                               c.c_int),
    }
    for fname, (argtypes, restype) in sigs.items():
        fn = getattr(lib, fname)
        fn.argtypes = argtypes
        fn.restype = restype
    return lib


def _build():
    """Build under an exclusive file lock so concurrent processes never
    dlopen a half-written .so."""
    import fcntl
    native_dir = os.path.join(_repo_root(), 'native')
    os.makedirs(os.path.join(native_dir, 'build'), exist_ok=True)
    lock_path = os.path.join(native_dir, 'build', '.build.lock')
    with open(lock_path, 'w') as lock_f:
        fcntl.flock(lock_f, fcntl.LOCK_EX)
        try:
            if not os.path.exists(_lib_path()):
                subprocess.run(['make', '-C', native_dir],
                               check=True, capture_output=True)
        finally:
            fcntl.flock(lock_f, fcntl.LOCK_UN)


def load():
    """Load (building if needed) the native library.  On failure warns
    once with the cause and returns None (host rings then run the
    pure-Python core)."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get('BF_NO_NATIVE'):
            return None
        path = _lib_path()
        try:
            srcs = [os.path.join(_repo_root(), 'native', f)
                    for f in ('ring.cpp', 'capture.cpp',
                              'selftest.cpp', 'util.cpp')]
            stale = (not os.path.exists(path) or
                     any(os.path.exists(src) and
                         os.path.getmtime(src) > os.path.getmtime(path)
                         for src in srcs))
            if stale:
                if os.path.exists(path):
                    os.unlink(path)
                _build()
            _lib = _declare(ctypes.CDLL(path))
        except (OSError, AttributeError,
                subprocess.CalledProcessError) as e:
            _lib = None
            detail = getattr(e, 'stderr', None)
            if isinstance(detail, bytes):
                detail = detail.decode('utf-8', 'replace')
            warnings.warn(
                'native library %s could not be built or loaded; host '
                'rings fall back to the pure-Python core.  %s: %s%s'
                % (path, type(e).__name__, e,
                   '\n' + detail.strip()[-2000:] if detail else ''),
                RuntimeWarning, stacklevel=2)
        return _lib


_io_engine_supported = None


def io_engine_supported():
    """Whether the native IO engines (capture/transmit) are compiled in
    (the .so builds portable stubs on non-Linux that return errors)."""
    global _io_engine_supported
    if _io_engine_supported is None:
        lib = load()
        ok = False
        if lib is not None:
            import ctypes
            h = ctypes.c_void_p()
            # fmt 0 / fd -1: create validates only engine availability
            if lib.bft_transmit_create(ctypes.byref(h), 0, -1) == 0:
                lib.bft_transmit_destroy(h)
                ok = True
        _io_engine_supported = ok
    return _io_engine_supported


def available():
    return load() is not None


def check(status, what=''):
    if status < 0:
        raise NativeError("native ring error %d %s" % (status, what))
    return status
