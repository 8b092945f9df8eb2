"""The one general traffic generator.

A traffic mix is a data file under ``traffic/`` (see the README for its
keys); a configuration's ``input`` says what one gulp looks like.  From
those and ``--seed`` this module makes everything a run feeds: a pool
of distinct gulps, the order in which the source replays them, and
which products are compared and where.  Every seed gives the same
sizes and the same amount of work, in another order.
"""

import json
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ORDER_LEN = 1 << 16


def load(name):
    with open(os.path.join(HERE, 'traffic', name + '.json')) as f:
        mix = json.load(f)
    if mix.get('loop') != 'closed':
        raise ValueError('traffic %r: this generator drives closed '
                         'loops only (loop=%r)' % (name, mix.get('loop')))
    return mix


def make_pool(cfg, mix, seed):
    """``pool_gulps`` distinct host gulps in the input's storage dtype
    (ci8: a structured (re, im) int8 pair), one generator per gulp so
    that they can be drawn side by side."""
    inp = cfg['input']
    if inp['dtype'] != 'ci8':
        raise ValueError('the generator draws ci8 voltages; %r is not '
                         'taught yet' % inp['dtype'])
    shape = (cfg['gulp_nframe'],) + tuple(inp['frame_shape'])
    lo, hi = inp['range']
    ci8 = np.dtype([('re', np.int8), ('im', np.int8)])

    def one(i):
        rng = np.random.default_rng([int(seed), 1, i])
        raw = rng.integers(lo, hi, size=shape + (2,), dtype=np.int8)
        return raw.view(ci8).reshape(shape)

    n = int(mix['pool_gulps'])
    with ThreadPoolExecutor(max_workers=min(n, 4)) as ex:
        return list(ex.map(one, range(n)))


def replay_order(mix, seed):
    """Pool index of the k-th gulp offered, k modulo ORDER_LEN."""
    rng = np.random.default_rng([int(seed), 2])
    return rng.integers(0, int(mix['pool_gulps']), size=ORDER_LEN)


class Sampler(object):
    """Which part of each product is compared, the same for the sink
    that keeps it and the reference that recomputes it: the
    configuration's ``pick``, in full for a seeded one product in
    ``sample_one_in`` and a few frames of every other, so that a
    product that is missing, repeated or out of its place fails
    wherever it falls."""

    def __init__(self, cfg, mix, seed, pick):
        self.cfg, self.seed, self.pick = cfg, int(seed), pick
        self.one_in = int(mix['sample_one_in'])

    def where(self, k):
        """Index of the compared part of product ``k``."""
        rng = np.random.default_rng([self.seed, 3, int(k)])
        # product 0 in full and product 1 not, so that whatever taking
        # either kind of sample compiles is compiled in the warm-up
        full = k == 0 or self.one_in <= 1 or \
            (k != 1 and not rng.integers(0, self.one_in))
        return self.pick(rng, self.cfg, full)
