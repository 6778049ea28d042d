"""Kernel tasks: jitted jax/Pallas callables as device-typed tasks.

`kernel_task` turns a compute function into a `RemoteFunction` whose
resource request defaults to one device unit, so the scheduler places it
only on nodes declaring that capacity and the node's dedicated device
lane executes it. The wrapper:

- jit-compiles the function once (unless it is already jitted or
  ``jit=False``) — the Pallas ops wrappers in `repro.kernels` compile on
  TPU and interpret on the CPU themselves, so the same task runs in CI;
- optionally warms the compile cache at *registration* time
  (``warmup_args=``), so the first cluster dispatch measures dispatch,
  not tracing;
- blocks until the device has actually finished
  (`jax.block_until_ready`), inside a `kernel_task` span (host time from
  the call to the device's finish: dispatch and wait included, so not a
  kernel's device time, which only a profiler trace gives), which
  `profiler.summarize` folds into ``kernel_tasks`` /
  ``kernel_task_ms_mean``.

Device tasks run on the thread backend only: a chip belongs to one
process, so `core.init(backend="process")` refuses device capacity.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax

from repro.core import profiler
from repro.core.api import RemoteFunction
from repro.core.worker import current_task


def _block(out: Any) -> Any:
    """Wait for async device execution so the measured window covers the
    kernel, not just its dispatch. Leaves that are not jax arrays (numpy
    results, python scalars) have nothing to wait for; a device error
    raises here."""
    jax.block_until_ready([x for x in jax.tree.leaves(out)
                           if isinstance(x, jax.Array)])
    return out


def _instrument(fn, kernel_name: str):
    @functools.wraps(fn)
    def run(*args, **kwargs):
        spec = current_task()
        with profiler.span("kernel_task", "compute", kernel=kernel_name,
                           task=spec.task_id if spec else ""):
            return _block(fn(*args, **kwargs))
    return run


class KernelFunction(RemoteFunction):
    """A `RemoteFunction` whose payload is a (jitted) device kernel.

    `warm(*args)` runs the function once on the calling thread and
    blocks on the result — compile caches are per-process, so warming on
    the driver covers every thread-backend worker.
    """

    def __init__(self, fn, *, resources: Optional[Dict[str, float]] = None,
                 num_returns: int = 1, jit: bool = True,
                 static_argnames: Optional[Tuple[str, ...]] = None,
                 max_retries: int = -1, retry_exceptions=None,
                 backoff: float = 0.0, deadline: float = 0.0):
        self.kernel_fn = fn
        if jit and not hasattr(fn, "lower"):
            fn = jax.jit(fn, static_argnames=static_argnames)
        self._compiled = fn
        super().__init__(_instrument(fn, getattr(fn, "__name__",
                                                 repr(fn))),
                         num_returns=num_returns,
                         resources=({"gpu": 1.0} if resources is None
                                    else resources),
                         max_retries=max_retries,
                         retry_exceptions=retry_exceptions,
                         backoff=backoff, deadline=deadline)

    def warm(self, *args, **kwargs) -> "KernelFunction":
        _block(self._compiled(*args, **kwargs))
        return self


def kernel_task(fn=None, *, resources: Optional[Dict[str, float]] = None,
                num_returns: int = 1, jit: bool = True,
                static_argnames: Optional[Tuple[str, ...]] = None,
                warmup_args: Optional[tuple] = None,
                max_retries: int = -1, retry_exceptions=None,
                backoff: float = 0.0,
                deadline: float = 0.0):
    """Decorator/factory: ``@kernel_task`` or
    ``kernel_task(fn, resources={"tpu": 1}, warmup_args=(x, y))``."""
    def wrap(f) -> KernelFunction:
        kf = KernelFunction(f, resources=resources,
                            num_returns=num_returns, jit=jit,
                            static_argnames=static_argnames,
                            max_retries=max_retries,
                            retry_exceptions=retry_exceptions,
                            backoff=backoff, deadline=deadline)
        if warmup_args is not None:
            kf.warm(*warmup_args)
        return kf
    if fn is None:
        return wrap
    return wrap(fn)
