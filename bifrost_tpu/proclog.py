"""ProcLog: filesystem-based runtime status publishing.

Every block publishes small status files under ``$BF_PROCLOG_DIR``
(default ``/dev/shm/bifrost_tpu``)``/<instance>/<block>/<log>``, which
the CLI tools (like_top, pipeline2dot) render.  Mirrors the reference
mechanism (reference: src/proclog.cpp:45-147,
python/bifrost/proclog.py:40-143), including stale-PID garbage
collection on startup.

``<instance>`` is the bare PID by default.  A fabric launcher
(``bifrost_tpu.fabric``, docs/fabric.md) stamps a host identity —
``<pid>@<hostname>.<role>`` — via :func:`set_identity` (or the
``BF_FABRIC_IDENTITY`` env var, ``hostname.role``), so N launcher
processes on DIFFERENT hosts sharing one filesystem (NFS state dirs,
shared /tmp) never collide on a recycled PID or interleave each
other's logs.  Stale-instance GC only ever probes PIDs of entries
stamped with the LOCAL hostname (or unstamped ones): a remote host's
live pipeline must not be reaped because its PID happens to be dead
here.
"""

from __future__ import annotations

import os
import shutil
import socket as socket_mod
import threading

__all__ = ['ProcLog', 'load_by_pid', 'load_by_filename',
           'set_identity', 'get_identity', 'instance_name']

_lock = threading.Lock()
_gc_done = False

#: (hostname, fabric role) stamped into this process's proclog
#: instance directory; None = bare-PID layout
_identity = None


def set_identity(host=None, role=None):
    """Stamp this process's proclog tree (and telemetry snapshot) with
    a host identity: subsequent ProcLogs land under
    ``<pid>@<host>.<role>`` instead of the bare PID.  Called by the
    fabric launcher before any block is constructed; ``None``/``None``
    clears the stamp.  Separators are sanitized out of the parts so
    the instance name stays one path component."""
    global _identity
    if host is None and role is None:
        _identity = None
        return None

    def _clean(part, fallback, dots=True):
        part = str(part or fallback)
        part = part.replace(os.sep, '-').replace('@', '-')
        if not dots:
            # the role is the LAST dot-separated token of the entry
            # (hostnames may be dotted FQDNs) — it must stay dot-free
            part = part.replace('.', '-')
        return part or fallback
    _identity = (_clean(host, socket_mod.gethostname() or 'host'),
                 _clean(role, 'worker', dots=False))
    return _identity


def get_identity():
    """The (hostname, role) stamp in effect, or None.  Reads
    ``BF_FABRIC_IDENTITY`` (``hostname.role``) once when nothing was
    set programmatically — how launcher subprocesses inherit the
    stamp."""
    global _identity
    if _identity is None:
        env = os.environ.get('BF_FABRIC_IDENTITY', '').strip()
        if env:
            host, _, role = env.partition('.')
            set_identity(host or None, role or 'worker')
    return _identity


def instance_name(pid=None):
    """This process's proclog directory entry: ``<pid>`` bare, or
    ``<pid>@<host>.<role>`` under a fabric identity."""
    pid = os.getpid() if pid is None else int(pid)
    ident = get_identity()
    if ident is None:
        return str(pid)
    return '%d@%s.%s' % (pid, ident[0], ident[1])


def entry_pid(entry):
    """The PID encoded in a proclog instance entry (bare or
    identity-stamped), or None for foreign files."""
    head = str(entry).split('@', 1)[0]
    return int(head) if head.isdigit() else None


def entry_host(entry):
    """The hostname stamped into an instance entry, or None (bare
    layout)."""
    if '@' not in str(entry):
        return None
    tail = str(entry).split('@', 1)[1]
    return tail.rsplit('.', 1)[0] if '.' in tail else tail


def proclog_dir():
    base = os.environ.get('BF_PROCLOG_DIR')
    if base is None:
        base = '/dev/shm/bifrost_tpu' if os.path.isdir('/dev/shm') \
            else os.path.join(os.path.expanduser('~'), '.bifrost_tpu',
                              'proclog')
    return base


def _pid_exists(pid):
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def _gc_stale():
    """Remove proclog trees of dead LOCAL processes (reference:
    proclog.cpp ProcLogMgr stale-PID cleanup).  Entries stamped with
    another host's identity are left alone — their PIDs are
    meaningless here."""
    base = proclog_dir()
    if not os.path.isdir(base):
        return
    local = socket_mod.gethostname()
    for entry in os.listdir(base):
        pid = entry_pid(entry)
        if pid is None:
            continue
        host = entry_host(entry)
        if host is not None and host != local:
            continue
        if not _pid_exists(pid):
            shutil.rmtree(os.path.join(base, entry), ignore_errors=True)


class ProcLog(object):
    #: minimum seconds between file writes per log (BF_PROCLOG_INTERVAL;
    #: 0 writes every update).  like_top & co. poll at ~1 Hz, so
    #: throttling saves an open+rename in every block's per-gulp hot
    #: loop without losing observability.
    MIN_INTERVAL = None

    def __init__(self, name):
        global _gc_done
        self.name = name
        self.path = os.path.join(proclog_dir(), instance_name(), name)
        if ProcLog.MIN_INTERVAL is None:
            try:
                ProcLog.MIN_INTERVAL = float(
                    os.environ.get('BF_PROCLOG_INTERVAL', '0.1'))
            except ValueError:
                ProcLog.MIN_INTERVAL = 0.1
        self._last_write = 0.0
        with _lock:
            if not _gc_done:
                try:
                    _gc_stale()
                except OSError:
                    pass
                _gc_done = True
        try:
            os.makedirs(os.path.dirname(self.path), exist_ok=True)
        except OSError:
            pass

    def ready(self):
        """Whether the next (non-forced) :meth:`update` would pass the
        rate limiter — lets hot loops skip computing expensive
        contents that update() would drop anyway (e.g. the per-gulp
        latency percentiles in pipeline.py)."""
        import time as time_mod
        if not ProcLog.MIN_INTERVAL:
            return True
        return (time_mod.monotonic() - self._last_write >=
                ProcLog.MIN_INTERVAL)

    def update(self, contents, force=False):
        """Write ``key : value`` lines (dict) or a raw string.  Writes
        are rate-limited to MIN_INTERVAL per log unless ``force``."""
        import time as time_mod
        now = time_mod.monotonic()
        if not force and ProcLog.MIN_INTERVAL and \
                now - self._last_write < ProcLog.MIN_INTERVAL:
            return
        self._last_write = now
        if isinstance(contents, dict):
            text = ''.join('%s : %s\n' % (k, v) for k, v in contents.items())
        else:
            text = str(contents)
        # a span: on a slow filesystem (anything but the default
        # tmpfs) this is milliseconds on a block's own thread
        from .telemetry import spans
        with spans.timed('proclog.write', 'host', 'proclog.write_s'):
            try:
                tmp = self.path + '.tmp'
                with open(tmp, 'w') as f:
                    f.write(text)
                os.replace(tmp, self.path)
            except OSError:
                pass

    def close(self):
        pass


def _parse_value(v):
    v = v.strip()
    for cast in (int, float):
        try:
            return cast(v)
        except ValueError:
            continue
    return v


def load_by_filename(path):
    """Parse one proclog file into a dict
    (reference: proclog.py:69-91)."""
    out = {}
    with open(path, 'r') as f:
        for line in f:
            if ':' not in line:
                continue
            k, _, v = line.partition(':')
            out[k.strip()] = _parse_value(v)
    return out


def _resolve_instance(pid):
    """Instance directory entry for ``pid``: the bare PID dir when it
    exists, else the first identity-stamped entry carrying that PID.
    A full entry string passes through unchanged."""
    base = proclog_dir()
    entry = str(pid)
    if '@' in entry or os.path.isdir(os.path.join(base, entry)):
        return entry
    try:
        for cand in sorted(os.listdir(base)):
            if entry_pid(cand) == int(entry):
                return cand
    except (OSError, ValueError):
        pass
    return entry


def load_by_pid(pid, include_rings=False):
    """Parse all proclogs of a process into
    {block: {log: {key: value}}} (reference: proclog.py:93-143).
    ``pid`` may be a bare PID or a full ``<pid>@<host>.<role>``
    instance entry (fabric identity layout)."""
    root = os.path.join(proclog_dir(), _resolve_instance(pid))
    contents = {}
    for dirpath, _, filenames in os.walk(root):
        for fname in filenames:
            if fname.endswith('.tmp'):
                continue
            path = os.path.join(dirpath, fname)
            block = os.path.relpath(dirpath, root)
            try:
                parsed = load_by_filename(path)
            except (OSError, ValueError):
                continue
            contents.setdefault(block, {})[fname] = parsed
    return contents
