from __future__ import annotations

from repro.kernels.ssm_scan.kernel import ssm_scan as _kernel
from repro.kernels.ssm_scan.ref import ssm_scan_ref
from repro.kernels.platform import use_interpret


def ssm_scan(x, dt, b_t, c_t, a, d, *, bd: int = 128, bc: int = 256,
             backend: str = "auto"):
    if backend == "ref":
        return ssm_scan_ref(x, dt, b_t, c_t, a, d)
    return _kernel(x, dt, b_t, c_t, a, d, bd=bd, bc=bc,
                   interpret=use_interpret(backend))
