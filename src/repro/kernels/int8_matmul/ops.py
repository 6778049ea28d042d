from __future__ import annotations

from repro.kernels.int8_matmul.kernel import int8_matmul as _kernel
from repro.kernels.int8_matmul.ref import int8_matmul_ref, quantize_weights
from repro.kernels.platform import use_interpret


def int8_matmul(x, wq, scales, *, backend: str = "auto", **blocks):
    if backend == "ref":
        return int8_matmul_ref(x, wq, scales)
    return _kernel(x, wq, scales, interpret=use_interpret(backend),
                   **blocks)
