"""Where a Pallas kernel runs: compiled by Mosaic on a TPU, interpreted
everywhere else (the CPU test suite and CPU rehearsals)."""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """The kernel wrappers' ``interpret`` flag. ``None`` (every program
    path) resolves from the default backend: compiled on a TPU, interpreted
    elsewhere. Only a test that compiles for a described, unattached TPU
    passes ``False`` by hand, because its process still sees the CPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
