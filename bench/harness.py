"""What the run loop hands a driver, and what a driver hands back.

A driver (`drivers/<name>.py`) exposes `run(ctx) -> Output`: it sets up
the program for the cell, drives it for `ctx.seconds` inside
`ctx.window()`, and checks what the timed path produced against the plain
reference. The run loop (`run.py`) turns the Output into the result line;
the metric readers (`metrics/*.py`) read `Output.data`.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from functools import cached_property
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

import jax

from bench.refmath import control_precision
from bench.spec import Cell, SpecError
from bench.devtrace import (DeviceTrace, SPAN_PREFIX, WINDOW_SPAN,
                            read_trace_dir)


def span(name: str, **stats):
    """A harness host span, written into the profiler's trace when one
    is being recorded (and next to free otherwise)."""
    return jax.profiler.TraceAnnotation("bench:" + name, **stats)


@dataclass
class Check:
    """One number compared with its limit: correct when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Output:
    e2e: Dict[str, float]               # end-to-end metric values
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: Optional[int]
    data: Dict[str, Any] = field(default_factory=dict)  # for the readers
    notes: Dict[str, Any] = field(default_factory=dict)  # stderr only


@dataclass
class Context:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    started: float                      # process start, time.time()
    setup_s: Optional[float] = None     # set when the window opens
    device_trace: Optional[DeviceTrace] = None
    memory_peak: Optional[int] = None   # bytes in use, in the window
    gc_pauses: List[float] = field(default_factory=list)  # s, in window
    _trace_dir: Optional[str] = None

    def mark(self, what: str) -> None:
        """Say on standard error, at once, that a phase of the run has
        ended, in seconds since the process started."""
        print(f"bench: {what} at {time.time() - self.started:.1f} s",
              file=sys.stderr, flush=True)

    @property
    def model(self) -> Dict[str, Any]:
        return self.cell.config["model"]

    @property
    def control(self) -> str:
        """The control's precision: one step below the products the
        configuration states (`refmath.lower`)."""
        return control_precision(self.cell.config["precision"]["products"])

    @cached_property
    def family(self) -> ModuleType:
        return self.cell.family()

    def program_config(self):
        """The registry's configuration with the file's sizes; a size
        that differs from the registry must be listed in `reduced`."""
        from repro.configs.registry import get_config
        base = get_config(self.cell.config["registry"])
        have = dataclasses.asdict(base)
        over: Dict[str, Any] = {}
        for k, v in self.model.items():
            if k not in have:
                raise SpecError(f"config key {k!r} is not a ModelConfig "
                                f"field")
            cur = have[k]
            if isinstance(cur, dict):
                if cur != v:
                    over[k] = type(getattr(base, k))(**v)
            elif cur != v:
                over[k] = v
        unlisted = set(over) - set(self.cell.config.get("reduced", []))
        if unlisted:
            raise SpecError(f"{sorted(unlisted)} differ from the registry's "
                            f"{base.name} but are not listed in `reduced`")
        return base.scaled(**over) if over else base

    @contextmanager
    def window(self):
        """The measured window: setup_s ends where it opens. With
        `--trace 1` the profiler records all of it, marked by the window
        span. Bytes in use on the cell's chips are sampled all through
        it, so `memory_peak` is the window's and not set-up's."""
        # set-up's objects leave the collector's reach: a full collection
        # over them inside the window would stall every harness thread
        gc.collect()
        gc.freeze()
        if self.trace:
            self._trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            # the harness's spans and the runtime's, not every Python call
            # (the Python tracer would slow the host path it measures), and
            # not the programs' HLO, which no reader needs
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        sampler = MemorySampler(jax.devices()[: self.cell.chips])
        gc_started = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                gc_started[0] = time.perf_counter()
            else:
                self.gc_pauses.append(time.perf_counter() - gc_started[0])
        self.setup_s = time.time() - self.started
        self.mark("set-up done")
        sampler.start()
        gc.callbacks.append(on_gc)
        try:
            with span(WINDOW_SPAN[len(SPAN_PREFIX):]):
                yield
        finally:
            gc.callbacks.remove(on_gc)
            self.memory_peak = sampler.stop()
            self.mark("window closed")
            if self.trace:
                jax.profiler.stop_trace()
                self.mark("trace stopped")

    def read_trace(self) -> None:
        """Reduce the recorded trace (after the run's checks, so its
        reading is not set-up or window time) and delete its files."""
        if self._trace_dir is None:
            return
        try:
            self.device_trace = read_trace_dir(Path(self._trace_dir),
                                               self.mark)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


class MemorySampler(threading.Thread):
    """The most bytes in use on any of `devices`, sampled every PERIOD_S
    from `start` to `stop` (None where the backend keeps no count)."""
    PERIOD_S = 0.02

    def __init__(self, devices):
        super().__init__(name="bench-memory", daemon=True)
        self.devices = devices
        self.peak: Optional[int] = None
        self._done = threading.Event()

    def _sample(self) -> None:
        for d in self.devices:
            n = (d.memory_stats() or {}).get("bytes_in_use")
            if n is not None and (self.peak is None or n > self.peak):
                self.peak = n

    def run(self) -> None:
        while True:
            self._sample()
            if self._done.wait(self.PERIOD_S):
                return

    def stop(self) -> Optional[int]:
        self._done.set()
        self.join()
        self._sample()
        return self.peak


def process_peak(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip since the process started,
    set-up included, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free(tree) -> None:
    """Delete the device buffers of a tree the harness made."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()
