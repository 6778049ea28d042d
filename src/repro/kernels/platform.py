"""How the Pallas ops pick between a compiled kernel and interpret mode."""
from __future__ import annotations

import jax


def use_interpret(backend: str) -> bool:
    """Whether a Pallas op runs in interpret mode.

    ``auto`` compiles the kernel on TPU and interprets it on the CPU (the
    tests); any other platform raises rather than silently interpreting.
    ``pallas`` and ``interpret`` force one side."""
    if backend == "auto":
        platform = jax.default_backend()
        if platform == "tpu":
            return False
        if platform == "cpu":
            return True
        raise RuntimeError(
            f"no Pallas backend for platform {platform!r}: 'auto' compiles "
            f"on tpu and interprets on cpu")
    if backend not in ("pallas", "interpret"):
        raise ValueError(f"unknown kernel backend {backend!r}: expected "
                         f"auto | pallas | interpret | ref")
    return backend == "interpret"
