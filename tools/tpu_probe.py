"""Probe the accelerator backend with a hard deadline.

Prints one JSON line {"alive": bool, "init_s": float, "platform": str}
and exits 0 only when the backend BOTH initializes within the deadline
AND passes a bf16 matmul correctness gate (a chip that initializes but
miscomputes must not trigger a bench capture); exits 3 otherwise.
Used by bench.py's retry loop and by round automation to decide when
the chip is healthy enough for a capture session.  The probe process
holds the chip while it runs: a chip belongs to one process at a time,
so run it before, never beside, the process that will use the chip.
"""
import json
import os
import sys
import time


def main():
    deadline = float(os.environ.get('BF_PROBE_DEADLINE', '120'))
    t0 = time.time()
    result = {}

    def probe():
        # JAX_PLATFORMS alone selects the backend (chip_smoke.py fact
        # iii), so the probe gates on the same one the bench will use
        import jax
        devs = jax.devices()
        import jax.numpy as jnp
        x = jnp.ones((256, 256), jnp.bfloat16)
        # accumulate the check sum in f32: a backend that reduces in
        # bf16 would round 2^24 + 256 terms and fail an exact compare
        # while being perfectly healthy
        y = float(jnp.sum(x @ x, dtype=jnp.float32))
        expected = 256.0 * 256 * 256
        result['platform'] = devs[0].platform
        result['n_devices'] = len(devs)
        result['matmul_ok'] = abs(y - expected) <= 1e-3 * expected

    import threading
    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(deadline)
    init_s = round(time.time() - t0, 1)
    if result.get('platform') and result.get('matmul_ok'):
        print(json.dumps(dict(result, alive=True, init_s=init_s)))
        return 0
    # preserve whatever the probe did collect: a live-but-miscomputing
    # chip (platform set, matmul_ok false) must be distinguishable in
    # watch logs from a 120 s init hang (nothing set)
    print(json.dumps(dict(result, alive=False, init_s=init_s)))
    return 3


if __name__ == '__main__':
    sys.exit(main())
