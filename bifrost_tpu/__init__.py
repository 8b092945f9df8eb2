"""bifrost_tpu — a TPU-native stream-processing framework for
high-throughput radio astronomy, with the capabilities of
ledatelescope/bifrost re-designed for JAX/XLA.

Architecture (see SURVEY.md for the reference layer map):

- ring buffer runtime + thread-per-block pipeline (host side)
- every device op is a jit-compiled function over gulp-shaped arrays
- device memory space 'tpu' holds jax.Arrays; XLA replaces NVRTC as the
  JIT engine; jax collectives over an ICI mesh replace point-to-point
  GPU transports for scale-out

Usage mirrors the reference::

    import bifrost_tpu as bf
    bc = bf.BlockChainer()
    bc.blocks.read_sigproc(['obs.fil'], gulp_nframe=16384)
    bc.blocks.copy('tpu')
    bc.blocks.fft(axes='freq', axis_labels='fine_freq')
    bc.blocks.detect('stokes')
    bc.blocks.copy('system')
    bc.blocks.write_sigproc()
    bf.get_default_pipeline().run()
"""

__version__ = '0.4.0'

from .dtype import DataType
from .space import Space, SPACES
from .ndarray import (ndarray, asarray, empty, zeros, empty_like, zeros_like,
                      copy_array, memset_array)
from .ring import (Ring, EndOfDataStop, WouldBlock, RingPoisonedError,
                   split_shape, ring_view)
from .pipeline import (Pipeline, BlockScope, Block, SourceBlock,
                       MultiTransformBlock, TransformBlock, SinkBlock,
                       get_default_pipeline, get_current_block_scope,
                       block_scope, block_view, PipelineInitError)
from .supervision import PipelineRuntimeError, PipelineStallError
from .block_chainer import BlockChainer
from . import device
from . import memory
from . import proclog
from .ops.map import map  # noqa: A001  (shadows builtin by design, like bf.map)
from .ops.map import clear_map_cache, list_map_cache
from .ops.reduce import reduce  # noqa: A001  (bf.reduce, like the reference)
from .ops.transpose import transpose
from .ops.quantize import quantize, unpack
from .io import udp_socket
from .io.udp_socket import Address as address  # bf.address alias

from . import ops
from . import blocks
from . import views
from . import stages
from . import parallel
from . import io
from . import trace
from . import telemetry
from . import supervision
from . import autotune
# NOTE: the service tier (bifrost_tpu.service, docs/service.md) and
# the fabric (bifrost_tpu.fabric) are imported on demand — telemetry
# snapshots gate their sections on the module being loaded, so a
# plain pipeline process never pays for (or reports) the layers it
# does not use.
from . import testing
from .utils import EnvVars, ObjectCache, enable_compilation_cache
from .header_standard import enforce_header_standard
