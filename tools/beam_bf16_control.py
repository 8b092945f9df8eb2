#!/usr/bin/env python3
"""The second control of the benchmark's cell ``beamform-tab-replay``:
the program's own lossy path in the program's place.

    python3 tools/beam_bf16_control.py --workload beamform-tab-replay \\
        --seed <n> --seconds <s> --trace 0

runs ``perfbench/run.py`` with ``BF_BEAM_IMPL=pallas_bf16`` set after
the harness has cleared the ``BF_*`` variables: the fused kernel then
meets the FLOAT weights, rounded to bfloat16, in one pass of the MXU
where the deployment's int8 weights are exact.  A weight of 8
significant bits is off by up to 2^-9 of itself, a beam's summed
power by a few parts in 10^4, which at a value of 64 moves the values
nearest a rounding boundary across it: the run must end
``correct: false`` (PERF.md section 6, PR 35, has the readings).  The
configuration's ``--control`` is the reference on voltages cut to four
bits; its ``control_env`` is empty, so this one needs a wrapper.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, 'perfbench'))

import run                                           # noqa: E402


def main():
    set_environment = run.set_environment

    def forced(control_env):
        set_environment(control_env)
        os.environ['BF_BEAM_IMPL'] = 'pallas_bf16'
    run.set_environment = forced
    return run.main()


if __name__ == '__main__':
    sys.exit(main())
