"""Test configuration: run everything on the JAX CPU backend with 8
virtual devices, so multi-chip sharding tests exercise a real Mesh without
TPU hardware (the 'CPU-only matrix row' of the reference CI,
reference: .github/workflows/main.yml:20-24)."""

import os

flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = \
        (flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ['JAX_PLATFORMS'] = 'cpu'
os.environ.setdefault('BF_PROCLOG_DIR', '/tmp/bifrost_tpu_test_proclog')
