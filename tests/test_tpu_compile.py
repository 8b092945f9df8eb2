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


def _loop_copies(text):
    """Names of the ``copy`` instructions in the body of the program's
    largest while loop (a chunk loop's)."""
    comps, name = {}, None
    for line in text.splitlines():
        m = re.match(r'(?:ENTRY )?%([\w.\-]+) .*\{$', line)
        if m:
            name, comps[m.group(1)] = m.group(1), []
        elif line == '}':
            name = None
        elif name:
            comps[name].append(line)
    bodies = re.findall(r' while\(.*?body=%([\w.\-]+)', text)
    body = max((comps[b] for b in bodies), key=len)
    return [re.match(r'\s*%([\w.\-]+) = ', line).group(1)
            for line in body if ' copy(' in line]


def _one_axis_of_words(text, count, nbytes):
    """The program's ``count`` outputs are ONE axis of uint32 words of
    ``nbytes`` each, laid out as the host holds them (``T(1024)``), and
    no output packs sub-word elements (a tile with a second level,
    ``(4,1)`` or ``(2,1)``)."""
    root = [line for line in text.splitlines() if 'ROOT' in line][-1]
    assert root.count('u32[%d]{0:T(1024)}' % (nbytes // 4)) == count
    assert not re.search(r'T\(\d+(,\d+)?\)\(\d,1\)', root)


@pytest.mark.parametrize('form', ['planes', 'complex64'])
def test_cut_of_an_xcorr_product(one_chip, form):
    """From planes: slices and one interleave, each piece ONE axis of
    uint32 words, no complex type, temporaries under one piece (the
    interleave is made as rows first: asked for one axis at once, the
    compiler put the (re, im) axis minor in tiles of 128 lanes, 64
    times the piece).  From complex64 (the control, and what a
    mesh-scoped product still pays): the split of all 2.1 GB first."""
    if form == 'planes':
        comp = _compiled_cut(one_chip, [_XCORR] * 2, np.float32,
                             1, 8, 8, True)
    else:
        comp = _compiled_cut(one_chip, [_XCORR], np.complex64,
                             1, 8, 8, True)
    text, mem = comp.as_text(), comp.memory_analysis()
    split = len(re.findall(r'custom_call_target="X64(Split|Combine)',
                           text))
    _one_axis_of_words(text, 8, 16 << 20)
    if form == 'planes':
        assert split == 0 and 'c64' not in text
        assert mem.temp_size_in_bytes < 16 << 20
    else:
        assert split >= 2
        assert mem.temp_size_in_bytes >= int(np.prod(_XCORR)) * 4
    assert mem.output_size_in_bytes >= 8 * (16 << 20)


def test_cut_of_a_gpuspec_product_is_slices_alone(one_chip):
    """Sixteen pieces of 1024 frames, each ONE axis of uint32 words: the
    slices and the relayout of each into the host's order, a copy on
    the device, and nothing else; no custom call, no temporary."""
    comp = _compiled_cut(one_chip, [_GPUSPEC], np.float32,
                         0, 1024, 16, False)
    text, mem = comp.as_text(), comp.memory_analysis()
    assert 'custom-call' not in text and mem.temp_size_in_bytes == 0
    assert text.count('dynamic-slice') >= 16 and 'c64' not in text
    _one_axis_of_words(text, 16, 16 << 20)


def test_cut_of_a_gpuspec_hsr_product(one_chip):
    """The 1 GiB (1, 64, 4, 2^20) product in pieces of one coarse
    channel, eight to a program: each ONE axis of uint32 words, no
    custom call, no temporary."""
    class Product(object):
        shape, nbytes, sharding = _HSR_PRODUCT, 1 << 30, \
            type('S', (), {'device_set': {0}})
    assert xfer._piece_plan(Product) == (1, 1)
    assert not xfer._as_rows(_HSR_PRODUCT)
    comp = _compiled_cut(one_chip, [_HSR_PRODUCT], np.float32,
                         1, 1, 8, False)
    text, mem = comp.as_text(), comp.memory_analysis()
    assert 'custom-call' not in text and mem.temp_size_in_bytes == 0
    assert 'c64' not in text
    _one_axis_of_words(text, 8, 16 << 20)


# ---------------------------------------------------------------------------
# gpuspec-hsr: a 2^20-point transform of 64 channels x 2 pol, and the sum
# ---------------------------------------------------------------------------

#: the gpuspec-hsr cell's gulp in device representation, and its product
_HSR_GULP = (1, 64, 2, 1 << 20, 2)
_HSR_PRODUCT = (1, 64, 4, 1 << 20)
#: the copies of the long spectrometer's chunk loop, by name, as the
#: libtpu of jax 0.9.0 compiles it
HSR_LOOP_COPIES = {'words': ['copy.%d' % k for k in range(48, 54)],
                   'pairs': ['copy.%d' % k for k in range(56, 62)]}


@pytest.mark.parametrize('form', ['pairs', 'words'])
def test_long_transform_chain_of_a_gpuspec_hsr_gulp(one_chip, form):
    """FftStage -> DetectStage('stokes') as FusedBlock composes them,
    at the deployment's shape: the long spectrometer, three levels of
    matrix products in a loop over 64 chunks of one coarse channel,
    no FFT call, no complex type, no VMEM or layout refusal; beside
    the 1 GiB of Stokes it writes, the voltages' int8 planes (copied
    and relaid: 0.13 GB each) and a chunk's temporaries, not the 1 GiB
    of a gulp's spectra.  From the gulp's int16 words on one axis,
    as a gulp on one device is held (PR 34), the same, the planes
    made by one pass of shifts and a chunk sliced where it lies:
    less in temporaries than from the pairs."""
    import jax
    from bifrost_tpu.stages import (FftStage, DetectStage, walk_headers,
                                    compose_stages, from_words)
    hdr = {'_tensor': {'shape': [-1, 64, 2, 1 << 20], 'dtype': 'ci8',
                       'labels': ['time', 'freq', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 4, 'units': [None] * 4}}
    stages = [FftStage('fine_time'), DetectStage('stokes')]
    fn, info = compose_stages(stages, walk_headers(stages, hdr),
                              _HSR_GULP, np.dtype('int8'))
    assert info == {'impl': 'long-spectrometer',
                    'fft': {'path': 'long', 'factors': [128, 64, 128],
                            'precision': 'high', 'nfft': [1 << 20]}}
    if form == 'words':
        fn, arg = from_words(fn, _HSR_GULP), jax.ShapeDtypeStruct(
            (128 << 20,), np.int16, sharding=one_chip)
    else:
        arg = jax.ShapeDtypeStruct(_HSR_GULP, np.int8, sharding=one_chip)
    comp = jax.jit(fn).trace(arg).lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    if form == 'words':
        # the words land as the host holds them
        assert 's16[134217728]{0:T(1024)(128)(2,1)} parameter(0)' in text
    assert ' fft(' not in text and 'c64' not in text
    assert text.count('convolution(') >= 12     # four products a level
    assert re.search(r'while\(', text)          # the loop over chunks
    # the forward in frequency order, as before the coherent fold kept
    # its spectra in the levels' order: the same relayouts, by name
    assert sorted(_loop_copies(text)) == HSR_LOOP_COPIES[form]
    assert mem.output_size_in_bytes == int(np.prod(_HSR_PRODUCT)) * 4
    # 0.67 GB as compiled from the pairs; from the words 0.40: the
    # planes are made on one axis and a chunk is sliced where it lies
    assert mem.temp_size_in_bytes <= (7 << 26 if form == 'words'
                                      else 3 << 28)


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


# ---------------------------------------------------------------------------
# a ci8 gulp as int16 words: what stands between the entry parameter and
# the spectrometer's kernel, and what the correlator's program holds
# ---------------------------------------------------------------------------

#: the gpuspec cells' gulp, (time, pol, fine_time), and the xcorr cell's,
#: (time, freq, station, pol): complex ci8 samples
_GPUSPEC_GULP = (16384, 2, 4096)
_XCORR_GULP = (512, 1024, 256, 2)


def _entry(text):
    """``{name: (opcode, operand names)}`` of the entry computation."""
    ops = {}
    for line in text[text.index('ENTRY'):].splitlines():
        m = re.match(r'\s*(?:ROOT )?%([\w.\-]+) = .*?\s([\w\-]+)\((.*)',
                     line)
        if m:
            ops[m.group(1)] = (m.group(2),
                               re.findall(r'%([\w.\-]+)', m.group(3)))
    return ops


def _in_front_of_the_kernel(text):
    """Opcodes from the Mosaic kernel's first operand back to the
    entry parameter (bitcasts move nothing and are left out)."""
    ops = _entry(text)
    kernel = [name for name, (op, args) in ops.items()
              if op == 'custom-call' and args and
              'tpu_custom_call' in text[text.index('%' + name + ' = '):]
              .split('\n', 1)[0]]
    assert len(kernel) == 1, kernel
    chain, at = [], ops[kernel[0]][1][0]
    while ops[at][0] != 'parameter':
        if ops[at][0] != 'bitcast':
            chain.append(ops[at][0])
        at = ops[at][1][0]
    return chain


@pytest.mark.parametrize('form', ['words', 'rows', 'pairs'])
def test_what_stands_in_front_of_the_spectrometer_kernel(one_chip, form):
    """The gpuspec chain's kernel at the cell's full gulp and its own
    configuration there (tile 16, three bf16 passes, the transpose in
    the epilogue).  From the gulp's words on one axis, as a ring holds
    them, ONE pass folds them to the kernel's rows.  From the rows
    themselves the ``tpu_custom_call``'s first operand IS the entry
    parameter.  From int8 (re, im) pairs, the control and what a
    mesh-scoped gulp still pays, the device interleaves them again
    and restores the host's order of axes: four passes over the
    gulp."""
    import jax
    from bifrost_tpu.ops import spectrometer as spec
    ntime, _npol, nfft = _GPUSPEC_GULP
    arg = jax.ShapeDtypeStruct(
        *{'words': ((2 * ntime * nfft,), np.int16),
          'rows': ((2 * ntime, nfft), np.int16),
          'pairs': (_GPUSPEC_GULP + (2,), np.int8)}[form],
        sharding=one_chip)
    comp = jax.jit(lambda v: spec.fused_spectrometer(
        v, nfft=nfft, rfactor=4, time_tile=16, precision='high',
        transpose='epilogue')).trace(arg).lower().compile()
    text = comp.as_text()
    front = _in_front_of_the_kernel(text)
    if form == 'words':
        assert front == ['reshape']
        # one axis lands as the host holds it: no tiles of rows to make
        assert 's16[134217728]{0:T(1024)(128)(2,1)} parameter(0)' in text
        assert 'bitcast-convert' not in text
    elif form == 'rows':
        assert front == []
        assert 's16[32768,4096]{1,0:T(8,128)(2,1)} parameter(0)' in text
    else:
        assert front == ['reshape', 'copy', 'bitcast-convert', 'fusion']
        # (re, im) is far from minor-most in what the runtime lands
        assert 's8[16384,2,4096,2]{2,0,3,1:T(8,128)(4,1)} parameter(0)' \
            in text
    assert comp.memory_analysis().output_size_in_bytes == ntime * 4096 * 4


def test_correlator_gulp_program_from_words(one_chip):
    """The correlator's in-place program of a gulp (every gulp but the
    first of an integration) at the xcorr cell's shape, from the
    gulp's words on one axis: one pass folds them to (time, freq,
    station x pol), the engine's own order, and the planes of a chunk
    of channels are two shifts of its slice: temporaries larger than
    from the pairs by that one folded gulp, the donated accumulator
    planes still the output."""
    import jax
    from bifrost_tpu.blocks.correlate import CorrelateBlock
    from bifrost_tpu.ops.linalg import XEngine
    blk = CorrelateBlock.__new__(CorrelateBlock)     # no pipeline here
    blk.engine = XEngine(accuracy='f32', impl=None)
    nchan = _XCORR_GULP[1]
    acc = jax.ShapeDtypeStruct((1, nchan, 256, 2, 256, 2), np.float32,
                               sharding=one_chip)
    mems = {}
    for form, arg in (
            ('pairs', (_XCORR_GULP + (2,), np.int8)),
            ('words', ((int(np.prod(_XCORR_GULP)),), np.int16))):
        fn = blk._build_in_place(_XCORR_GULP + (2,), True, False,
                                 form == 'words')
        comp = fn.trace(jax.ShapeDtypeStruct(*arg, sharding=one_chip),
                        acc, acc).lower().compile()
        text, mems[form] = comp.as_text(), comp.memory_analysis()
        if form == 'words':
            ops = _entry(text)
            loop = [args for op, args in ops.values() if op == 'while']
            assert len(loop) == 1
            assert {ops[a][0] for a in ops[loop[0][0]][1]} <= \
                {'parameter', 'reshape', 'constant', 'copy'}
            assert 's16[268435456]{0:T(1024)(128)(2,1)} parameter(0)' \
                in text
    gulp = int(np.prod(_XCORR_GULP)) * 2
    assert mems['words'].temp_size_in_bytes <= \
        mems['pairs'].temp_size_in_bytes + gulp
    for mem in mems.values():
        assert mem.alias_size_in_bytes == 2 * int(np.prod(acc.shape)) * 4


# ---------------------------------------------------------------------------
# beamform-tab: 864 Stokes-I beams of 64 channels x 64 dishes x 2 pol
# ---------------------------------------------------------------------------

#: the beamform-tab cell's gulp (time, freq, station, pol) and weights
_TAB_GULP = (16384, 64, 64, 2)
_TAB_WEIGHTS = (64, 2, 864, 64)


def test_beamformer_gulp_program_of_a_beamform_tab_gulp(one_chip,
                                                        monkeypatch):
    """[BeamformStage(int8, a weight set per channel), DetectStage(
    'stokes_i'), ReduceStage('time', 16), QuantizeStage('u8')] as
    FusedBlock composes it, at the deployment's shape, from the
    gulp's int16 words: ONE Mosaic kernel (a channel and 512 frames a
    program) with int8 dots and int32 sums inside, one pass in front
    of it (the fold of the words to rows of frames), no complex type;
    the weights are the program's second ARGUMENT (58.7 MB, widened),
    not a constant folded into it; beside the 56.6 MB product the
    temporaries are that one folded gulp (268 MB) and the product's
    own relayout: under 0.4 GB, where the beam voltages of a gulp
    would be 14.5 GB."""
    import jax
    from bifrost_tpu.ops import pallas_kernels as pk
    from bifrost_tpu.stages import (BeamformStage, DetectStage,
                                    ReduceStage, QuantizeStage,
                                    walk_headers, compose_stages)
    # off the chip the kernel would be interpreted: compile it
    monkeypatch.setattr(pk, '_xcorr_interpret', lambda interpret: False)
    hdr = {'_tensor': {'shape': [-1] + list(_TAB_GULP[1:]), 'dtype': 'ci8',
                       'labels': ['time', 'freq', 'station', 'pol'],
                       'scales': [[0, 1]] * 4, 'units': [None] * 4}}
    rng = np.random.default_rng(0)
    w = np.exp(2j * np.pi * rng.random(_TAB_WEIGHTS, dtype=np.float32)) \
        .astype(np.complex64)
    stages = [BeamformStage(w, accuracy='int8'), DetectStage('stokes_i'),
              ReduceStage('time', 16), QuantizeStage('u8', 1.1e-5)]
    plan, info = compose_stages(stages, walk_headers(stages, hdr),
                                _TAB_GULP + (2,), np.dtype('int8'))
    assert info['impl'] == 'pallas-beamform-detect'
    assert (info['weights'], info['dot'], info['time_tile']) == \
        ('per channel', 'int8', 512)
    fn, operands = plan.bound()
    (wide,) = operands
    assert wide.shape == (64, 256, 4 * 896) and wide.dtype == np.int8
    args = (jax.ShapeDtypeStruct((int(np.prod(_TAB_GULP)),), np.int16,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct(wide.shape, wide.dtype,
                                 sharding=one_chip))
    traced = jax.jit(fn).trace(*args)
    kernel = str(traced.jaxpr)
    assert kernel.count('pallas_call') == 1
    assert kernel.count('dot_general') == 4 == \
        kernel.count('preferred_element_type=int32')   # one a section
    assert 'i8[512,256]' in kernel          # z = [re | im] of a tile
    comp = traced.lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert _in_front_of_the_kernel(text) == ['reshape']
    assert 's16[134217728]{0:T(1024)(128)(2,1)} parameter(0)' in text
    assert re.search(r's8\[64,256,3584\]\S* parameter\(1\)', text)
    assert 'c64' not in text and 'bitcast-convert' not in text
    assert mem.argument_size_in_bytes >= (256 << 20) + wide.size
    assert mem.output_size_in_bytes == 1024 * 64 * 864
    assert mem.generated_code_size_in_bytes < (4 << 20)
    assert mem.temp_size_in_bytes < 400e6


def test_cut_of_a_beamform_tab_product_is_words_in_one_program(one_chip):
    """The 56.6 MB u8 product (1024, 64, 1, 864) crosses in four
    pieces of 256 rows, all cut by one program (xfer._piece_plan), each
    ONE axis of uint32 words: four bytes of a row to a word, as the
    host holds them.  The rows come first (xfer._as_rows: an axis of
    one before the last lets the compiler lay a piece out time-minor),
    then the fold of the last axis.  Where the compiler does not allow
    a temporary no larger than a piece: it packs bytes into words by
    widening each to a u32 first and shifting, so a piece's bytes stand
    in HBM four times over as u32 for a moment (a fold of the whole
    piece to (-1, 4) asked for 32 times them, the lanes padded)."""
    class Product(object):
        shape, nbytes, sharding = (1024, 64, 1, 864), 1024 * 64 * 864, \
            type('S', (), {'device_set': {0}})
    axis, step = xfer._piece_plan(Product)
    assert (axis, step) == (0, 256) and xfer._as_rows(Product.shape)
    assert not xfer._as_rows(_GPUSPEC) and xfer._as_rows(_XCORR)
    comp = _compiled_cut(one_chip, [Product.shape], np.uint8,
                         0, step, 4, True)
    text, mem = comp.as_text(), comp.memory_analysis()
    piece = 256 * 64 * 864
    _one_axis_of_words(text, 4, piece)
    assert 'c64' not in text and 'custom-call' not in text
    # the libtpu of jax 0.9.0: 0.17 GB, twelve pieces' worth, of the
    # first two pieces' widened bytes and relayouts (the other two
    # stand in VMEM)
    assert mem.temp_size_in_bytes < 13 * piece
    entry = text[text.index('ENTRY'):]
    sizes = [int(np.prod([int(n) for n in shape.split(',')])) * 4
             for shape in re.findall(r'u32\[([\d,]+)\]', entry)]
    assert max(sizes) == 4 * piece


# ---------------------------------------------------------------------------
# guppi-fold: coherent dedispersion and fold of one node's 64 channels
# ---------------------------------------------------------------------------

#: the guppi-fold cell's gulp: one RAW block of 64 channels x 2 pol,
#: 8192 samples of overlap and 917 504 new; its product
_FOLD_GULP = (1, 64, 2, 925696)
_FOLD_PRODUCT = (1, 64, 4, 2048)


def test_coherent_fold_program_of_a_guppi_fold_gulp(one_chip, monkeypatch):
    """[CoherentDedispersionStage, DetectStage('stokes'), FoldStage] as
    FusedBlock composes it, at the deployment's shape, from the gulp's
    int16 words on one axis: the one program, both 2^16-point
    transforms on the three-level path (four products a level, three
    levels, two transforms, six bf16 passes a product), no FFT call,
    no complex type, a loop over the channels; the chirp's two planes
    and the start residue are ARGUMENTS, the stages' gulp operands in
    the chain's order (the chirp's 33.5 MB folded in as constants would
    be in the executable, and a residue as a constant would compile
    every gulp).  The fold reads the Stokes series where the loop wrote
    them (ops.fold.cut_path, steered to the chip's choice here: off
    the chip the backend is the CPU's): one kernel, and no relayout of
    the series from the loop's four-row tiles, no padded copy and no
    array of its 275 rotations, which took 10.4 ms of the v5e's
    56.35 a gulp; temporaries the Stokes series and little more, not
    a gulp's spectra or dedispersed voltages (8.6 GB and 0.94 GB as
    float32 planes)."""
    import jax
    from bifrost_tpu.ops import fold
    from bifrost_tpu.stages import (CoherentDedispersionStage, DetectStage,
                                    FoldStage, walk_headers, compose_stages)
    monkeypatch.setattr(fold, '_on_tpu', lambda: True)
    hdr = {'_tensor': {'shape': [-1] + list(_FOLD_GULP[1:]), 'dtype': 'ci8',
                       'labels': ['time', 'freq', 'pol', 'fine_time'],
                       'scales': [[0, 1], [1100.78125, 1.5625], [0, 1],
                                  [0, 0.64e-6]],
                       'units': [None, 'MHz', None, 's']}}
    stages = [CoherentDedispersionStage(297.5, 65536, 8192),
              DetectStage('stokes'), FoldStage(2048, 335923, 100)]
    plan, info = compose_stages(stages, walk_headers(stages, hdr),
                                _FOLD_GULP + (2,), np.dtype('int8'))
    assert info['impl'] == 'coherent-fold' and info['fold'] == 'kernel'
    path = {'path': 'long', 'factors': [32, 16, 128],
            'precision': 'highest', 'nfft': [65536], 'order': 'levels'}
    assert info['fft'] == dict(path, inverse=path)
    operands = [op for s in stages for op in s.gulp_operands(0, 1)]
    nword = int(np.prod(_FOLD_GULP))
    args = [jax.ShapeDtypeStruct((nword,), np.int16, sharding=one_chip)] + \
        [jax.ShapeDtypeStruct(op.shape, op.dtype, sharding=one_chip)
         for op in operands]
    assert [a.shape for a in args[1:]] == [(64, 65536), (64, 65536), (1,)]
    comp = jax.jit(plan.words).trace(*args).lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert 's16[%d]{0:T(1024)(128)(2,1)} parameter(0)' % nword in text
    assert re.search(r's32\[1\]\{0[^}]*\} parameter\(3\)', text)
    entry = text[text.index('ENTRY'):]
    assert entry.count('f32[64,65536]{1,0:T(8,128)} parameter(') == 2
    assert ' fft(' not in text and 'c64' not in text
    assert text.count('convolution(') >= 24
    assert text.count('operand_precision={highest,highest}') >= 24
    assert re.search(r'while\(', text)
    # the spectrum stays in the levels' order between the transforms:
    # no digit reversal, which put it in frequency order with k1 (32
    # wide) minor, padded to 128 lanes, four such copies a chunk and
    # the inverse's input folded back, 40 copies in the loop
    assert 'f32[64,128,16,32]' not in text
    assert len(_loop_copies(text)) <= 36
    # the loop writes the fold's rows in eight-row tiles, and the kernel
    # reads them there: no four-row Stokes, no pad or reshape to a
    # (256, ...) series, no array of the rotations
    assert 'f32[32,2,4,917504]' not in text
    assert 'f32[275,256,3360]' not in text
    assert not re.search(r'= f32\[256,\d+\]\{[^}]*\} (pad|reshape)\(',
                         text)
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert mem.output_size_in_bytes == int(np.prod(_FOLD_PRODUCT)) * 4
    assert mem.argument_size_in_bytes >= nword * 2 + 2 * 64 * 65536 * 4
    # the Stokes series (0.94 GB) and little else: 1.89 GB with the
    # rotations cut by XLA
    stokes = 64 * 4 * 917504 * 4
    assert stokes < mem.temp_size_in_bytes < 5 * stokes // 4


@pytest.mark.parametrize('core', ['xla', 'pallas'])
def test_fdmt_program_of_a_bl_frb_search_gulp(one_chip, core):
    """The FDMT block's program with negative delays at the deployment's
    shape, 512 channels of [4096 held ‖ 2048 new] filterbank samples
    over 4096 delays, with each core the race may take: one module
    named for it (``jit_fdmt_history``, which a device trace shows), the
    gulp's DM-time plane and the next history out.  Pallas's steps
    whose rows pass the VMEM budget run ``_xla_merge_step`` (three
    kernels, then six row-take steps): all nine as kernels, Mosaic
    refused the fourth (16.12 MiB of a 16 MiB scoped limit)."""
    import jax
    from bifrost_tpu.blocks.fdmt import history_program
    from bifrost_tpu.ops.fdmt import Fdmt
    plan = Fdmt().init(512, 4096, 1100.0, 2.9296875 / 8)
    make = {'xla': plan._core_jax, 'pallas': plan._core_pallas}[core]
    prog = jax.jit(history_program(make(True), 4096))
    args = [jax.ShapeDtypeStruct((512, n), np.float32, sharding=one_chip)
            for n in (4096, 2048)]
    comp = prog.trace(*args).lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert text.startswith('HloModule jit_fdmt_history')
    assert text.count('custom_call_target="tpu_custom_call"') == \
        (3 if core == 'pallas' else 0)
    # the two arrays, and the tuple's table of them
    assert 0 <= mem.output_size_in_bytes - (4096 * 2048 + 512 * 4096) * 4 \
        <= 1024
    # the levels' rows over 6144 frames: about 100 MB a level
    assert mem.temp_size_in_bytes < 1.5e9


def test_channeliser_program_of_a_bl_frb_search_gulp(one_chip):
    """The FRB search's front ([FftStage('fine_time', shifted),
    DetectStage('stokes_i'), ReduceStage('time', 128), TransposeStage])
    as FusedBlock composes it, at the deployment's shape, from the
    gulp's int16 words on one axis: the row channeliser, the words
    taken as the rows of 128 they lie in (one block-diagonal product a
    chunk of rows, three bf16 passes), no FFT call, no array with the
    transform's 8 points minor, which the ``xla-fused`` composition
    padded 16 times in 128-lane tiles (``u16[262144,64,2,8]``, 8.6 GB)
    and turned time-minor, 9.13 GB of temporaries; the 4 MB product
    in the corner turn's order."""
    import jax
    from bifrost_tpu.stages import (DetectStage, FftStage, ReduceStage,
                                    TransposeStage, compose_stages,
                                    from_words, walk_headers)
    hdr = {'_tensor': {'shape': [-1, 64, 2, 8], 'dtype': 'ci8',
                       'labels': ['time', 'freq', 'pol', 'fine_time'],
                       'scales': [[0, 1]] * 4, 'units': [None] * 4}}
    stages = [FftStage('fine_time', axis_labels='fine_freq',
                       apply_fftshift=True),
              DetectStage('stokes_i'), ReduceStage('time', 128),
              TransposeStage(['pol', 'freq', 'fine_freq', 'time'])]
    shape = (262144, 64, 2, 8, 2)
    fn, info = compose_stages(stages, walk_headers(stages, hdr), shape,
                              np.dtype('int8'))
    assert info == {'impl': 'row-channeliser', 'nfft': 8, 'shift': True,
                    'precision': 'split'}
    arg = jax.ShapeDtypeStruct((1 << 28,), np.int16, sharding=one_chip)
    comp = jax.jit(from_words(fn, shape)).trace(arg).lower().compile()
    text, mem = comp.as_text(), comp.memory_analysis()
    assert 's16[268435456]{0:T(1024)(128)(2,1)} parameter(0)' in text
    assert '[262144,64,2,8]' not in text
    assert ' fft(' not in text and 'c64' not in text
    assert re.search(r'ROOT .*= f32\[1,64,8,2048\]', text)
    assert mem.output_size_in_bytes == 64 * 8 * 2048 * 4
    assert mem.temp_size_in_bytes < 3e9
