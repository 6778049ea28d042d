"""Public wrapper: the Pallas kernel on TPU, interpret mode on CPU
(tests), or the pure-jnp reference."""
from __future__ import annotations

from repro.kernels.flash_attention.kernel import flash_attention as _kernel
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.platform import use_interpret


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128, backend: str = "auto"):
    """backend: auto | pallas | interpret | ref."""
    if backend == "ref":
        return attention_ref(q, k, v, causal=causal, window=window)
    return _kernel(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                   interpret=use_interpret(backend))
