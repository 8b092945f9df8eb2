"""With the timed path broken underneath, ``correct`` comes out false.

The faults these cells can have: an answer altered where it is
produced; half of the batch left out (the rest delivered as if it were
all); and, for the delivery guarantee, two products delivered in each
other's place.  A state left unchanged and an exchange between chips left out
belong to training and to several chips: no cell here has them.
"""

from copy import deepcopy

import pytest

from util import rehearse

CELLS = ['gpuspec-replay', 'gpuspec-resident']


def tamper(where_wanted, fn):
    """wrap_chain that puts a device block applying ``fn(k, array)``
    to the k-th span at the chain's input or output."""
    from bifrost_tpu.pipeline import TransformBlock

    class Tamper(TransformBlock):
        def __init__(self, iring):
            super(Tamper, self).__init__(iring)
            self.k = 0

        def define_valid_input_spaces(self):
            return ('tpu',)

        def on_sequence(self, iseq):
            return deepcopy(iseq.header)

        def on_data(self, ispan, ospan):
            out = fn(self.k, ispan.data)
            self.k += 1
            if out is None:
                return 0
            ospan.set(out)

    def wrap(where, block):
        return Tamper(block) if where == where_wanted else block
    return wrap


def altered(k, x):
    """One product in three is off by a part in a thousand."""
    return x if k % 3 else x * (1 + 1e-3)


def second_half_zero(k, x):
    """At the chain's input: the second half of every gulp's frames
    never reaches it.  At its output: they were never computed."""
    return x.at[x.shape[0] // 2:].set(0)


@pytest.mark.parametrize('workload', CELLS)
def test_answer_altered(workload):
    res = rehearse(workload, wrap_chain=tamper('output', altered))
    assert res['correct'] is False, res['checks']
    assert res['failed'] > 0


@pytest.mark.parametrize('workload', CELLS)
def test_half_of_the_batch_left_out(workload):
    res = rehearse(workload,
                   wrap_chain=tamper('input', second_half_zero))
    assert res['correct'] is False, res['checks']


@pytest.mark.parametrize('workload', CELLS)
def test_half_of_the_product_not_computed(workload):
    res = rehearse(workload,
                   wrap_chain=tamper('output', second_half_zero))
    assert res['correct'] is False, res['checks']


@pytest.mark.parametrize('workload', CELLS)
def test_products_delivered_in_exchanged_order(workload):
    """Two neighbouring gulps that differ change places on their way
    into the chain: every product arrives, two of them in each other's
    place."""
    import run
    import traffic
    from bifrost_tpu.devrep import to_device_rep
    seed = 1
    _, cell, cfg, _ = run.load_cell(workload)
    cfg = run.merge(cfg, cfg['rehearse'])
    mix = traffic.load(cell['traffic'])
    pool = traffic.make_pool(cfg, mix, seed)
    order = traffic.replay_order(mix, seed)
    first = next(k for k in range(3, 64) if order[k] != order[k + 1])

    def exchange(k, x):
        if k in (first, first + 1):
            other = pool[order[2 * first + 1 - k]]
            return to_device_rep(other, cfg['input']['dtype'])
        return x
    res = rehearse(workload, seed=seed,
                   wrap_chain=tamper('input', exchange))
    assert res['correct'] is False, res['checks']
    assert res['checks']['products_missing']['value'] == 0
    assert res['checks']['products_over_limit']['value'] == 2
