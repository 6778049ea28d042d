from __future__ import annotations

from repro.kernels.mlstm_scan.kernel import mlstm_scan as _kernel
from repro.kernels.mlstm_scan.ref import mlstm_ref
from repro.kernels.platform import use_interpret


def mlstm_scan(q, k, v, log_i, log_f, *, bc: int = 128,
               backend: str = "auto"):
    if backend == "ref":
        return mlstm_ref(q, k, v, log_i, log_f)
    return _kernel(q, k, v, log_i, log_f, bc=bc,
                   interpret=use_interpret(backend))
