"""Process-level setup shared by the entry points (``chip_smoke.py``,
``launch/serve.py``, fleet replicas, the serving benchmark): JAX's
persistent compile cache, and which process owns the TPU.

Tests never call ``enable_compile_cache``: a compile made for a described,
unattached TPU is written to the cache but cannot be read back there.
"""

from __future__ import annotations

import os
import sys

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_root() -> str:
    """The directory holding ``src/`` (three levels above this package)."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(os.path.dirname(here)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache`` (gitignored): a directory that moves between
    runs never hits, so the path never comes from a temp name, a pid or
    the time."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout_root(), ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def tpu_backend_live() -> bool:
    """True once this process has initialised a TPU backend — it then
    holds every chip of the host until it exits. Never initialises one."""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def host_tpu_chips() -> int:
    """TPU chips attached to this host over PCI (no runtime is loaded)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]
