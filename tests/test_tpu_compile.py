"""The D2H cut programs, the long transform and the in-place sum at
the served cells' real sizes, compiled by libtpu's own compiler for a
described v5e (no chip: nothing runs).
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


# ---------------------------------------------------------------------------
# gpuspec-hsr: a 2^20-point transform of 64 channels x 2 pol, and the sum
# ---------------------------------------------------------------------------

#: the gpuspec-hsr cell's gulp in device representation, and its product
_HSR_GULP = (1, 64, 2, 1 << 20, 2)
_HSR_PRODUCT = (1, 64, 4, 1 << 20)


def test_long_transform_chain_of_a_gpuspec_hsr_gulp(one_chip):
    """FftStage -> DetectStage('stokes') as FusedBlock composes them,
    at the deployment's shape: the long spectrometer, three levels of
    matrix products in a loop over 64 chunks of one coarse channel,
    no FFT call, no complex type, no VMEM or layout refusal; beside
    the 1 GiB of Stokes it writes, the voltages' int8 planes (copied
    and relaid: 0.13 GB each) and a chunk's temporaries, not the 1 GiB
    of a gulp's spectra."""
    import jax
    from bifrost_tpu.stages import (FftStage, DetectStage, walk_headers,
                                    compose_stages)
    hdr = {'_tensor': {'shape': [-1, 64, 2, 1 << 20], 'dtype': 'ci8',
                       'labels': ['time', 'freq', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 4, 'units': [None] * 4}}
    stages = [FftStage('fine_time'), DetectStage('stokes')]
    fn, info = compose_stages(stages, walk_headers(stages, hdr),
                              _HSR_GULP, np.dtype('int8'))
    assert info == {'impl': 'long-spectrometer',
                    'fft': {'path': 'long', 'factors': [128, 64, 128],
                            'precision': 'high', 'nfft': [1 << 20]}}
    comp = jax.jit(fn).trace(jax.ShapeDtypeStruct(
        _HSR_GULP, np.int8, sharding=one_chip)).lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert ' fft(' not in text and 'c64' not in text
    assert text.count('convolution(') >= 12     # four products a level
    assert re.search(r'while\(', text)          # the loop over chunks
    assert mem.output_size_in_bytes == int(np.prod(_HSR_PRODUCT)) * 4
    assert mem.temp_size_in_bytes <= 3 << 28     # 0.67 GB as compiled


def test_in_place_sum_of_a_gpuspec_hsr_product(one_chip):
    """The accumulate block's program for every gulp but the first of
    an integration: the donated 1 GiB accumulator is the output, and
    nothing else is allocated."""
    import jax
    from bifrost_tpu.blocks.accumulate import gulp_program
    arg = jax.ShapeDtypeStruct(_HSR_PRODUCT, np.float32, sharding=one_chip)
    comp = gulp_program('f32', 'f32', first=False).trace(arg, arg) \
        .lower().compile()
    mem = comp.memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes == 1 << 30
    assert mem.temp_size_in_bytes == 0
