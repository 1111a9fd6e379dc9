"""Shared example plumbing: path setup + the compile cache."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup() -> None:
    """Examples run on whatever backend JAX gives this process — pin the
    CPU from outside with ``JAX_PLATFORMS=cpu`` (the launcher's ``--cpu``
    does it per rank). Compiles go to the persistent cache."""
    from parsec_tpu.utils import compile_cache
    compile_cache.enable()
