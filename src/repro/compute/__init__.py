"""Device-typed compute plane (the paper's R5): real jitted kernels as
first-class heterogeneous tasks over sharded parameters.

Three pieces on top of the core runtime:

- device placement (`repro.core.devices`): typed device resource keys
  ("gpu"/"tpu"/"accel") are hard capacity constraints in the scheduler,
  each device-holding node runs kernel tasks on a dedicated executor
  lane, and a request no declared node can ever satisfy seals promptly
  with `UnschedulableTaskError` under an explicit `node_resources=`
  topology;
- kernel tasks (`kernel.py`): `kernel_task` wraps a jax/Pallas callable
  into a `@remote`-style function that jit-warms at registration, runs
  on the device lane, blocks until the device is actually done, and
  is timed as a profiler `kernel_task` span
  (interpret-mode Pallas on CPU, so everything runs in CI);
- sharded parameters (`params.py`): `ParamSet` packs a model pytree
  into contiguous per-shard buffers living in the object store
  (refcounted, evictable, zero-copy readable), published as versioned
  handles in the control plane so consumers hot-swap weights.
"""
from repro.core.devices import (DEVICE_RESOURCE_KEYS,  # noqa: F401
                                device_keys, device_subset)
from repro.core.worker import UnschedulableTaskError  # noqa: F401
from repro.compute.kernel import KernelFunction, kernel_task  # noqa: F401
from repro.compute.params import ParamSet  # noqa: F401
