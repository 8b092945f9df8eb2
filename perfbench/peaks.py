"""The table of peaks, keyed by ``device_kind``.  A device that is not
in ``peaks.json`` is an error, never a default."""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load():
    with open(os.path.join(HERE, 'peaks.json')) as f:
        return json.load(f)


def for_device(kind):
    table = load()
    if kind not in table:
        raise KeyError('no peaks for device kind %r in peaks.json (%s)'
                       % (kind, ', '.join(sorted(table))))
    return table[kind]


def least_seconds(work, peak):
    """(seconds, bound): the least time the chip could take for
    ``work`` (a configuration's work() dict), and which peak sets it."""
    terms = {'bytes': work['bytes'] / peak['hbm_bytes_per_s'],
             'flops': work['flops'] / peak['bf16_flops_per_s'],
             'int8_ops': work['int8_ops'] / peak['int8_ops_per_s']}
    bound = max(terms, key=terms.get)
    return terms[bound], bound
