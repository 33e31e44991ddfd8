"""Repo-wide pytest config.

Single-core CI hosts deadlock the ``pure_callback`` serving path (XLA's CPU
client gets a one-thread pool there, and a host callback waiting on a jax
array starves the enclosing jit'd step).  ``ensure_host_callback_capacity``
injects ``--xla_force_host_platform_device_count=2`` into ``XLA_FLAGS``
before any test creates the CPU client, which gives the pool a second thread
and makes the emulated/guarded serving tests runnable everywhere.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "src"))

from repro.backend.base import ensure_host_callback_capacity  # noqa: E402

ensure_host_callback_capacity()
