"""FDMT tests (reference analogue: test/test_fdmt.py — slow-reference
oracle comparison, plus physical impulse tests)."""

import numpy as np
import pytest

from bifrost_tpu.ops.fdmt import Fdmt, fdmt_numpy, _cff


def test_jax_matches_numpy_oracle():
    nchan, max_delay, T = 16, 12, 64
    f0, df = 100.0, 1.0
    rng = np.random.RandomState(0)
    x = rng.rand(nchan, T).astype(np.float32)
    plan = Fdmt().init(nchan, max_delay, f0, df)
    out_jax = np.asarray(plan.execute(x))
    out_np = plan._core_numpy(x.astype(np.float64))
    np.testing.assert_allclose(out_jax, out_np, rtol=1e-5, atol=1e-4)


def test_non_power_of_two_channels():
    nchan, max_delay, T = 12, 8, 48
    rng = np.random.RandomState(1)
    x = rng.rand(nchan, T).astype(np.float32)
    plan = Fdmt().init(nchan, max_delay, 1400.0, 0.5)
    out_jax = np.asarray(plan.execute(x))
    out_np = plan._core_numpy(x.astype(np.float64))
    np.testing.assert_allclose(out_jax, out_np, rtol=1e-5, atol=1e-4)


def test_zero_dm_row_is_channel_sum():
    """Row 0 (no dispersion) must be the plain channel sum."""
    nchan, max_delay, T = 8, 6, 32
    rng = np.random.RandomState(2)
    x = rng.rand(nchan, T).astype(np.float32)
    plan = Fdmt().init(nchan, max_delay, 100.0, 1.0)
    out = np.asarray(plan.execute(x))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)


def test_dispersed_impulse_recovered():
    """A quadratically-dispersed impulse concentrates at its delay row."""
    nchan, max_delay, T = 32, 24, 128
    f0, df = 100.0, 1.0
    d_true = 16
    x = np.zeros((nchan, T), np.float32)
    band = _cff(f0, f0 + nchan * df, -2.0)
    t0 = 20
    for c in range(nchan):
        # delay of channel c relative to the bottom of the band
        delay = d_true * _cff(f0, f0 + c * df, -2.0) / band
        ti = t0 + int(round(delay))
        x[c, ti] = 1.0
    plan = Fdmt().init(nchan, max_delay, f0, df)
    out = np.asarray(plan.execute(x))
    # the peak over all (dm row, time) should be at (~d_true, t0) and
    # recover most of the nchan units of power
    row, t = np.unravel_index(np.argmax(out), out.shape)
    assert abs(row - d_true) <= 1
    assert abs(t - t0) <= 1   # tree delay rounding can shift by one
    assert out[row, t] >= 0.8 * nchan


def test_batched_execute():
    nchan, max_delay, T = 8, 6, 32
    rng = np.random.RandomState(3)
    x = rng.rand(3, nchan, T).astype(np.float32)
    plan = Fdmt().init(nchan, max_delay, 100.0, 1.0)
    out = np.asarray(plan.execute(x))
    assert out.shape == (3, max_delay, T)
    one = np.asarray(plan.execute(x[1]))
    np.testing.assert_allclose(out[1], one, rtol=1e-5)


def test_negative_delays():
    nchan, max_delay, T = 8, 6, 32
    rng = np.random.RandomState(4)
    x = rng.rand(nchan, T).astype(np.float32)
    plan = Fdmt().init(nchan, max_delay, 100.0, 1.0)
    out_jax = np.asarray(plan.execute(x, negative_delays=True))
    out_np = plan._core_numpy(x.astype(np.float64), negative_delays=True)
    np.testing.assert_allclose(out_jax, out_np, rtol=1e-5, atol=1e-4)


def test_fdmt_pallas_core_interpret_matches_oracle():
    """The Pallas FDMT step pipeline (default on TPU hardware) validated
    against the numpy oracle via interpret mode on CPU."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops.fdmt import Fdmt
    rng = np.random.RandomState(3)
    for (nchan, md, T, neg) in [(16, 12, 100, False), (8, 5, 64, True),
                                (13, 7, 130, False)]:
        x = rng.randn(nchan, T).astype(np.float32)
        plan = Fdmt().init(nchan, md, 1400.0, 0.1)
        core = plan._core_pallas(neg, interpret=True)
        out = np.asarray(jax.jit(core)(jnp.asarray(x)))
        ref = plan._core_numpy(x.astype(np.float64), neg)
        err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
        assert err < 1e-5, (nchan, md, T, neg, err)


def test_fdmt_pallas_smem_fallback_step_interpret(monkeypatch):
    """Steps whose delay tables exceed the SMEM budget run the XLA
    gather on the padded state; the mix must stay exact."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops import fdmt as fdmt_mod
    rng = np.random.RandomState(4)
    x = rng.randn(16, 100).astype(np.float32)
    plan = fdmt_mod.Fdmt().init(16, 12, 1400.0, 0.1)
    ref = plan._core_numpy(x.astype(np.float64), False)
    # force every step through the XLA fallback
    monkeypatch.setattr(fdmt_mod, 'SMEM_TABLE_BUDGET', 0)
    out = np.asarray(jax.jit(plan._core_pallas(False, interpret=True))(
        jnp.asarray(x)))
    err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 1e-5
    # and a half-and-half mix (first big step XLA, later small pallas)
    monkeypatch.setattr(fdmt_mod, 'SMEM_TABLE_BUDGET', 200)
    out = np.asarray(jax.jit(plan._core_pallas(False, interpret=True))(
        jnp.asarray(x)))
    err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 1e-5


def test_fdmt_pallas_vmem_fallback_step_interpret(monkeypatch):
    """Steps whose rows (two inputs and the output, all delays,
    double-buffered) exceed the VMEM budget run the XLA gather, as a
    512-channel, 4096-delay plan over 6144 frames needs from its fifth
    step on; the mix must stay exact."""
    import jax
    import jax.numpy as jnp
    from bifrost_tpu.ops import fdmt as fdmt_mod
    rng = np.random.RandomState(5)
    x = rng.randn(16, 100).astype(np.float32)
    plan = fdmt_mod.Fdmt().init(16, 12, 1400.0, 0.1)
    for neg in (False, True):
        ref = plan._core_numpy(x.astype(np.float64), neg)
        # the first steps' rows fit, the later steps' do not
        for budget in (0, 8 * 128 * 12):
            monkeypatch.setattr(fdmt_mod, 'VMEM_BLOCK_BUDGET', budget)
            out = np.asarray(jax.jit(plan._core_pallas(
                neg, interpret=True))(jnp.asarray(x)))
            err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
            assert err < 1e-5, (neg, budget)


def test_rolls_core_matches_oracle():
    """The static-roll core (BF_FDMT_IMPL=rolls) is exact against the
    numpy oracle across shapes, tails, and both delay signs."""
    import jax
    rng = np.random.RandomState(5)
    for (nchan, md, T, neg) in [(64, 37, 300, False), (7, 5, 64, False),
                                (33, 12, 100, True), (1, 4, 32, False)]:
        x = rng.randn(nchan, T).astype(np.float32)
        plan = Fdmt().init(nchan, md, 1400.0, -0.1)
        want = plan._core_numpy(x, negative_delays=neg)
        got = np.asarray(jax.jit(plan._core_jax_rolls(neg))(x))
        rel = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30)
        assert rel < 1e-4, (nchan, md, T, neg, rel)


def test_rolls_core_selected_by_env(monkeypatch):
    monkeypatch.setenv('BF_FDMT_IMPL', 'rolls')
    plan = Fdmt().init(32, 16, 1400.0, -0.1)
    core = plan._pick_core(False)
    assert core.__qualname__.startswith(
        Fdmt._core_jax_rolls.__qualname__)


def test_probe_selects_measured_winner(monkeypatch, tmp_path):
    """BF_FDMT_PROBE=1 oracle-gates and measures every candidate core
    at the actual shape through the shared mprobe harness (family
    'fdmt') and picks + caches the fastest (VERDICT r3 item 3: core
    choice is measured per (plan, backend), not asserted)."""
    from bifrost_tpu.ops import mprobe
    monkeypatch.setenv('BF_FDMT_PROBE', '1')
    monkeypatch.setenv('BF_CACHE_DIR', str(tmp_path))
    monkeypatch.setattr(mprobe, '_cache', {})
    plan = Fdmt().init(16, 8, 1400.0, -0.1)
    core = plan._pick_core(False, shape=(16, 128))
    assert plan.chosen_core in ('xla', 'rolls', 'pallas')
    assert plan.core_probe_ms
    assert plan.chosen_core == min(plan.core_probe_ms,
                                   key=plan.core_probe_ms.get)
    # the probed winner is a working core
    rng = np.random.RandomState(0)
    x = rng.rand(16, 128).astype(np.float32)
    got = np.asarray(core(x))
    want = plan._core_numpy(x.astype(np.float64))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    # disk cache written under the family file (a non-decisive or
    # errored race legitimately skips the write); a fresh plan with a
    # fresh in-process cache reads the winner back without
    # re-measuring when it was persisted
    monkeypatch.setattr(mprobe, '_cache', {})
    plan2 = Fdmt().init(16, 8, 1400.0, -0.1)
    plan2._pick_core(False, shape=(16, 128))
    if (tmp_path / 'fdmt.json').exists():
        assert plan2.chosen_core == plan.chosen_core
    else:
        assert plan2.chosen_core in ('xla', 'rolls', 'pallas')


def test_probe_refuses_when_the_gate_excludes_every_core(monkeypatch,
                                                        tmp_path):
    """A core the oracle gate excludes computes a wrong product: where
    the gate excludes every candidate, picking one stops with an error
    instead of running the heuristic's choice."""
    from bifrost_tpu.ops import mprobe
    monkeypatch.setenv('BF_FDMT_PROBE', '1')
    monkeypatch.setenv('BF_CACHE_DIR', str(tmp_path))
    monkeypatch.setattr(mprobe, '_cache', {})
    plan = Fdmt().init(16, 8, 1400.0, -0.1)
    monkeypatch.setattr(plan, '_candidate_cores', lambda neg: {
        'xla': lambda: (lambda x: 2.0 * plan._core_jax(neg)(x)),
        'rolls': lambda: (lambda x: plan._core_jax_rolls(neg)(x) + 1.0)})
    with pytest.raises(RuntimeError, match='every core departs'):
        plan._pick_core(False, shape=(16, 128))


def test_probe_off_keeps_heuristic(monkeypatch):
    monkeypatch.setenv('BF_FDMT_PROBE', '0')
    plan = Fdmt().init(16, 8, 1400.0, -0.1)
    plan._pick_core(False, shape=(16, 128))
    assert plan.chosen_core == 'rolls'
    assert plan.core_probe_ms is None
