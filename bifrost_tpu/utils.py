"""Small shared utilities mirroring the reference's native helpers."""

from __future__ import annotations

import os
import threading
from collections import OrderedDict

__all__ = ['EnvVars', 'ObjectCache']


class EnvVars(object):
    """Cached environment lookups (reference: src/EnvVars.hpp:34-42)."""

    _cache = {}
    _lock = threading.Lock()

    @classmethod
    def get(cls, name, default=None):
        with cls._lock:
            if name not in cls._cache:
                cls._cache[name] = os.environ.get(name, default)
            return cls._cache[name]

    @classmethod
    def clear(cls):
        with cls._lock:
            cls._cache.clear()


class ObjectCache(object):
    """Bounded LRU cache (reference: src/ObjectCache.hpp:1-94, used for
    the bfMap kernel cache)."""

    def __init__(self, capacity=128):
        self.capacity = capacity
        self._items = OrderedDict()
        self._lock = threading.Lock()

    def get(self, key, default=None):
        with self._lock:
            if key in self._items:
                self._items.move_to_end(key)
                return self._items[key]
            return default

    def put(self, key, value):
        with self._lock:
            self._items[key] = value
            self._items.move_to_end(key)
            while len(self._items) > self.capacity:
                self._items.popitem(last=False)
        return value

    def keys(self):
        with self._lock:
            return list(self._items.keys())

    def __contains__(self, key):
        with self._lock:
            return key in self._items

    def __len__(self):
        with self._lock:
            return len(self._items)

    def clear(self):
        with self._lock:
            self._items.clear()


def enable_compilation_cache():
    """Persist XLA compilations to disk (the analogue of the
    reference's on-disk map-kernel cache, src/map.cpp DiskCacheMgr):
    restarting a pipeline reuses compiled programs instead of paying
    first-compile latency again.  This is the ONE place the cache
    directory is decided: where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX reads it itself and nothing here sets a directory; where it is
    not, the cache is ``<checkout>/.jax_cache`` — a fixed path beside
    the package (the path is part of the cache key, so a directory
    that moves never hits).  Returns the directory in use.  Safe to
    call more than once."""
    import jax
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            '.jax_cache')
        os.makedirs(path, exist_ok=True)
        jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.5)
    return path
