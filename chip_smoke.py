"""Bring-up smoke: the serving and training paths on one TPU chip.

  python chip_smoke.py               # one chip: device, serve, train
  python chip_smoke.py --four-chips  # four chips: the sharded train step

Everything runs in this one process on the runtime's thread backend: a
chip belongs to one process. The phases, in order:

- device: JAX must see a TPU. Nothing here sets `JAX_PLATFORMS` or falls
  back to the CPU; with no TPU the script exits non-zero.
- serve: stablelm-1.6b at published widths (bf16, random weights from a
  seed) through the normal path, core.init -> FrontDoor -> ServingReplica
  actor -> ServingEngine -> Model. The first request goes alone and must
  match `ServingEngine.generate` of its prompt; every request must come
  back whole, with token ids inside the vocabulary.
- train: `examples/train_lm.py`'s compiled step graph (a device-typed
  grad-shard kernel task, reduce, AdamW apply) at xlstm-125m published
  widths, one shard. Every loss must be finite.
- four chips (`--four-chips`, and nothing else): three steps of
  `repro.launch.train`'s SPMD step for stablelm-1.6b on a 2x2
  `data x model` mesh. Its step-0 loss must match the unsharded model's
  loss on one chip, every loss must be finite, and the parameters must be
  spread over all four chips.

A failed check raises `SmokeCheckError`; no failure is caught. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

import train_lm  # noqa: E402
from repro import core  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.data.pipeline import batch_for_step  # noqa: E402
from repro.launch import train as train_launcher  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serving import FrontDoor, ServingEngine  # noqa: E402

SERVE_ARCH = "stablelm-1.6b"
TRAIN_STEPS = 4
TRAIN_ARGV = ["--arch", "xlstm-125m", "--full", "--shards", "1",
              "--steps", str(TRAIN_STEPS)]
# generous: a smoke checks answers, not latency; a ticket that runs past
# this raises instead of hanging the script
TICKET_TIMEOUT_S = 300.0
# relative agreement of two bf16 computations of one loss (bf16's epsilon)
BF16_RTOL = 2.0 ** -7


class SmokeCheckError(RuntimeError):
    """A phase produced a wrong result."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeCheckError(what)


# ------------------------------------------------------------------ serve

@dataclass
class ServeResult:
    vocab_size: int
    budget: int                      # new tokens asked of every request
    reference: List[int]             # ServingEngine.generate of prompt 0
    tokens: List[List[int]]          # served tokens, request 0 first
    weight_bytes: int


def serve_phase(cfg, *, n_requests: int = 8, lengths=(128, 256, 512),
                max_new: int = 16, max_batch: int = 2, max_seq: int = 1024,
                seed: int = 0) -> ServeResult:
    """Serve `n_requests` seeded prompts through the FrontDoor tier."""
    t0 = time.perf_counter()
    model = build_model(cfg)
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n), dtype=np.int32)
               for n in rng.choice(lengths, size=n_requests)]
    engine = ServingEngine(model, params, max_seq=max_seq)
    engine.warm([len(p) for p in prompts], max_batch)
    reference = engine.generate(prompts[0], max_new)
    print(f"serve: {cfg.name}, {weight_bytes} bytes of weights, prompt "
          f"lengths {[len(p) for p in prompts]}; set-up and compile "
          f"{time.perf_counter() - t0} s", flush=True)

    core.init(num_nodes=1, workers_per_node=2)
    try:
        fd = FrontDoor(lambda: engine, num_replicas=1, min_replicas=1,
                       max_replicas=1, max_batch=max_batch,
                       default_deadline_s=TICKET_TIMEOUT_S,
                       target_wave_s=TICKET_TIMEOUT_S,
                       resources={"cpu": 0.25})
        try:
            t1 = time.perf_counter()
            first = fd.submit(prompts[0], max_new).result(TICKET_TIMEOUT_S)
            tickets = [fd.submit(p, max_new) for p in prompts[1:]]
            rest = [t.result(TICKET_TIMEOUT_S) for t in tickets]
            wall_s = time.perf_counter() - t1
        finally:
            fd.close()
    finally:
        core.shutdown()
    tokens = [r.tokens for r in [first] + rest]
    print(f"serve: {len(tokens)} requests, {sum(map(len, tokens))} tokens "
          f"in {wall_s} s wall", flush=True)
    return ServeResult(cfg.vocab_size, max_new, reference, tokens,
                       weight_bytes)


def check_serve(r: ServeResult) -> None:
    _check(r.tokens[0] == r.reference,
           f"first request served {r.tokens[0]}, but ServingEngine."
           f"generate gives {r.reference}")
    for i, toks in enumerate(r.tokens):
        _check(len(toks) == r.budget,
               f"request {i}: {len(toks)} tokens, budget {r.budget}")
        _check(all(0 <= t < r.vocab_size for t in toks),
               f"request {i}: token id outside [0, {r.vocab_size}): {toks}")


def check_peak_memory(peak_bytes: Optional[int], weight_bytes: int) -> None:
    """The device must report a peak that covers at least the weights."""
    _check(peak_bytes is not None and peak_bytes >= weight_bytes,
           f"peak device memory {peak_bytes} bytes is below the "
           f"{weight_bytes} bytes of weights")


# ------------------------------------------------------------------ train

def train_phase(argv: List[str]) -> List[float]:
    """`examples/train_lm.py` as a user runs it; returns per-step losses."""
    return [loss for _, loss in train_lm.main(argv)]


def check_losses(losses: List[float], steps: int) -> None:
    _check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    _check(all(math.isfinite(x) for x in losses),
           f"non-finite loss: {losses}")


# ------------------------------------------------------------- four chips

@dataclass
class FourChipResult:
    reference_loss: float            # unsharded loss_fn on one chip
    losses: List[float]              # the SPMD steps' losses
    param_bytes: Dict[int, int]      # device id -> parameter bytes held


def four_chip_phase(cfg, *, seq_len: int = 1024, batch: int = 8,
                    steps: int = 3) -> FourChipResult:
    """The launcher's sharded step on a 2x2 mesh, with its reference."""
    first_batch = batch_for_step(
        train_launcher.data_config(cfg, seq_len=seq_len, batch=batch), 0)
    # the reference runs first and alone: the parameters fit one chip,
    # the optimizer state does not
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    reference = float(jax.jit(lambda p, b: model.loss_fn(p, b)[0])(
        params, first_batch))
    del params
    print(f"four chips: unsharded step-0 loss {reference}", flush=True)

    params, losses = train_launcher.train(
        cfg, make_host_mesh(model=2), steps=steps, batch=batch,
        seq_len=seq_len, log_every=1)
    held: Dict[int, int] = {}
    for leaf in jax.tree.leaves(params):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) \
                + shard.data.nbytes
    print(f"four chips: losses {losses}; parameter bytes by device {held}",
          flush=True)
    return FourChipResult(reference, losses, held)


def check_four_chip(r: FourChipResult, steps: int = 3,
                    min_device_bytes: float = 0.5e9) -> None:
    check_losses(r.losses, steps)
    _check(abs(r.losses[0] - r.reference_loss)
           <= BF16_RTOL * abs(r.reference_loss),
           f"sharded step-0 loss {r.losses[0]} vs unsharded "
           f"{r.reference_loss}")
    _check(len(r.param_bytes) == 4,
           f"parameters span {len(r.param_bytes)} devices, not 4")
    _check(all(b > min_device_bytes for b in r.param_bytes.values()),
           f"a device holds at most {min_device_bytes} parameter bytes: "
           f"{r.param_bytes}")


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded train step")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX finds no TPU; nothing was run",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chips:
        _check(len(devices) == 4, f"--four-chips needs 4 devices, "
                                  f"JAX sees {len(devices)}")
        check_four_chip(four_chip_phase(get_config(SERVE_ARCH)))
    else:
        served = serve_phase(get_config(SERVE_ARCH))
        check_serve(served)
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"serve: peak device memory {peak} bytes", flush=True)
        check_peak_memory(peak, served.weight_bytes)
        t0 = time.perf_counter()
        losses = train_phase(TRAIN_ARGV)
        print(f"train: losses {losses} in {time.perf_counter() - t0} s",
              flush=True)
        check_losses(losses, TRAIN_STEPS)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
