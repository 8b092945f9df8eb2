"""The degraded bench artifact's FINAL stdout line must stay within
what the driver's tail-capture parses (VERDICT r5 items 3/5:
`BENCH_r05.json parsed: null` — the one-line degraded JSON inlined the
whole probe history + watch-log tail).  bench.compact_degraded_line
caps the line at DEGRADED_LINE_LIMIT bytes with the detail in a side
file; these tests round-trip its output through the driver's parse
path (bench._last_json_line, which mirrors _run_isolated)."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))

import bench  # noqa: E402


def _fat_history(n=120):
    """A probe history big enough to defeat any naive inlining."""
    return [{'t': '2026-08-0%dT00:00:00Z' % (i % 9 + 1),
             'rc': 'timeout' if i % 3 else 1,
             'error': 'connection reset mid-handshake while probing the '
                      'accelerator backend attempt %d ' % i + 'x' * 200}
            for i in range(n)]


@pytest.fixture
def no_subprocesses(monkeypatch):
    """degraded_result shells out for host-only configs; stub it."""
    monkeypatch.setattr(
        bench, '_run_isolated',
        lambda argv, timeout=900, env_extra=None: {
            'config': 'stub config for %s' % argv[-1],
            'value': 1.23, 'unit': 'stub/s',
            'roofline': {'bound': 'stub ' * 40}})


def test_degraded_line_fits_and_roundtrips(tmp_path, no_subprocesses):
    result = bench.degraded_result(_fat_history())
    # simulate further bloat the real artifact carries
    result['watch_log_tail'] = ['probe[%d] rc=1 %s' % (i, 'y' * 160)
                                for i in range(12)]
    detail = str(tmp_path / 'detail.json')
    line_obj = bench.compact_degraded_line(result, detail_name=detail)
    line = json.dumps(line_obj)
    assert len(line) <= bench.DEGRADED_LINE_LIMIT
    # the driver's parse path accepts it
    parsed = bench._last_json_line('preamble noise\n' + line + '\n')
    assert parsed is not None
    assert parsed['metric'] == result['metric']
    assert 'error' in parsed
    assert parsed['value'] == 0.0 and parsed['vs_baseline'] == 0.0
    # history is truncated to counts + last entry, not inlined
    assert parsed['probe']['attempts'] == 120
    assert 'rc_counts' in parsed['probe']
    assert len(json.dumps(parsed.get('probe', {}))) < 1000
    # the full detail survives in the side file the line points to
    with open(detail) as f:
        full = json.load(f)
    assert len(full['probe_history']) == 120
    assert 'watch_log_tail' in full


def test_degraded_line_survives_pathological_error(tmp_path,
                                                   no_subprocesses):
    result = bench.degraded_result(_fat_history(400),
                                   reason='z' * 5000)
    line_obj = bench.compact_degraded_line(
        result, detail_name=str(tmp_path / 'd.json'))
    line = json.dumps(line_obj)
    assert len(line) <= bench.DEGRADED_LINE_LIMIT
    assert bench._last_json_line(line) is not None


def test_driver_parse_rejects_oversize_line():
    """The guard the compaction exists for: an over-limit line parses
    to None (the `parsed: null` failure mode, now caught in CI)."""
    fat = json.dumps({'metric': 'x', 'blob': 'y' * (2 * 4096)})
    assert bench._last_json_line(fat) is None


def test_last_json_line_skips_preamble_and_picks_last():
    text = '\n'.join([
        json.dumps({'chip_ceilings': {'hbm_gbs': 100.0}}),
        'INFO: some log line',
        json.dumps({'metric': 'old'}),
        json.dumps({'metric': 'new', 'value': 1}),
    ])
    parsed = bench._last_json_line(text)
    assert parsed == {'metric': 'new', 'value': 1}
