"""The D2H cut programs at the served cells' real sizes, compiled by
libtpu's own compiler for a described v5e (no chip: nothing runs).
What a program with a complex64 argument costs on the TPU, and what
the cut of planes spares, is a property of that compiler: a
whole-product split (two custom calls and a product's half in
temporaries) in front of the slices.  One file, and the topology in a
fixture: only the worker that runs these tests loads libtpu."""

import re

import numpy as np
import pytest

from bifrost_tpu import xfer

#: the xcorr cell's product, cut along its channels in pieces of 16 MiB
#: (8 channels), eight to a program
_XCORR = (1, 1024, 256, 2, 256, 2)
#: the gpuspec cell's: sixteen pieces of 1024 frames in one program
_GPUSPEC = (16384, 4, 1024)


@pytest.fixture(scope='module')
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as exc:
        pytest.skip('no v5e:2x2 topology can be described here: %s' % exc)
    return SingleDeviceSharding(topo.devices[0])


def _compiled_cut(one_chip, shapes, dtype, *static):
    import jax
    xfer._cut(np.zeros((4, 2), np.float32), 0, 0, 2, 2, False)  # builds it
    args = tuple(jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                 for shape in shapes)
    return xfer._cut_fn.lower(args, 0, *static).compile()


@pytest.mark.parametrize('form', ['planes', 'complex64'])
def test_cut_of_an_xcorr_product(one_chip, form):
    """From planes: slices and one interleave, no temporary, no
    complex type.  From complex64 (the control, and what a mesh-scoped
    product still pays): the split of all 2.1 GB first."""
    if form == 'planes':
        comp = _compiled_cut(one_chip, [_XCORR] * 2, np.float32,
                             1, 8, 8, True)
    else:
        comp = _compiled_cut(one_chip, [_XCORR], np.complex64,
                             1, 8, 8, True)
    text, mem = comp.as_text(), comp.memory_analysis()
    split = len(re.findall(r'custom_call_target="X64(Split|Combine)',
                           text))
    if form == 'planes':
        assert split == 0 and 'c64' not in text
        assert mem.temp_size_in_bytes == 0
    else:
        assert split >= 2
        assert mem.temp_size_in_bytes >= int(np.prod(_XCORR)) * 4
    assert mem.output_size_in_bytes >= 8 * (16 << 20)


def test_cut_of_a_gpuspec_product_is_slices_alone(one_chip):
    comp = _compiled_cut(one_chip, [_GPUSPEC], np.float32,
                         0, 1024, 16, False)
    text, mem = comp.as_text(), comp.memory_analysis()
    assert 'custom-call' not in text and mem.temp_size_in_bytes == 0
    assert text.count('dynamic-slice') >= 16
