"""What a run of a cell is made of: the context that drivers and metric
readers see, the window a driver returns, and the checks it makes."""
from __future__ import annotations

import dataclasses
import gc
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

from bench import data


class BenchError(RuntimeError):
    """A cell that cannot run here: unknown names, no TPU, too few
    chips, a configuration its traffic cannot take."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileStats:
    """Backend compilations (persistent-cache loads included) from JAX's
    monitoring events, split at the start of the window."""

    def __init__(self):
        from jax import monitoring
        self.setup_calls = self.window_calls = 0
        self.setup_s = self.window_s = 0.0
        self.cache_hits = 0
        self.in_window = False
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name, secs, **_):
        if name != "/jax/core/compile/backend_compile_duration":
            return
        if self.in_window:
            self.window_calls += 1
            self.window_s += secs
        else:
            self.setup_calls += 1
            self.setup_s += secs


class GcPauses:
    """Collector pauses inside the window (the collector holds the
    interpreter lock: the event loop and the dispatch threads stop)."""

    def __init__(self):
        self.count, self.longest_s, self.total_s = 0, 0.0, 0.0
        self.full = 0                       # of the oldest generation
        self.in_window = False
        self._t = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if not self.in_window:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            pause = time.perf_counter() - self._t
            self.count += 1
            self.full += info["generation"] == 2
            self.total_s += pause
            self.longest_s = max(self.longest_s, pause)


@dataclasses.dataclass
class Window:
    """What the measured window of a cell's loop produced.

    ``metrics`` are the end-to-end numbers (name -> value); ``extra``
    holds what the per-layer readers need (dispatch times, counters)."""

    seconds: float
    attempted: int
    failed: int
    metrics: dict
    extra: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Check:
    """One compared number and its limit: it passes at or under the
    limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    """What a driver and the metric readers see of a run."""

    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    traced: bool
    devices: list = dataclasses.field(default_factory=list)
    compile: Optional[CompileStats] = None
    gc: GcPauses = dataclasses.field(default_factory=GcPauses)
    control: bool = False
    setup_s: Optional[float] = None
    window: Optional[Window] = None
    trace: object = None
    log: Callable = log
    t_start: float = 0.0
    trace_dir: Optional[Path] = None
    _window_span: object = None

    def transform_kwargs(self) -> dict:
        """The engine arguments the configuration states; the control
        (``--control``) computes in bfloat16 instead of float32.
        Integer samples carry their precision (``bit_depth``) beside
        them, as JPEG 2000's SIZ marker does."""
        c = self.config
        kw = dict(wavelet=c["wavelet"], scheme=c["scheme"],
                  backend=c["backend"], fuse=c["fuse"],
                  compute_dtype="bfloat16" if self.control else c["dtype"])
        if data.integer_samples(c):
            kw["bit_depth"] = c["bit_depth"]
        return kw

    def annotate(self, name: str):
        """A host span in the profiler's trace (no-op untraced)."""
        import contextlib
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin_window(self) -> None:
        """Set-up ends here: the first timed call comes next."""
        self.setup_s = time.perf_counter() - self.t_start
        self.log(f"[setup] {self.setup_s:.3f} s; "
                 f"{self.compile.setup_calls} backend compiles in "
                 f"{self.compile.setup_s:.3f} s, "
                 f"{self.compile.cache_hits} persistent-cache hits")
        # set-up ends with one full collection, so that the window
        # starts from the same collector state whatever set-up left
        # behind; the collector runs as usual in the window, over the
        # whole heap (no freeze)
        gc.collect()
        self.gc.in_window = True
        if self.traced:
            import jax
            # under $TMPDIR, and removed once read: a traced decode
            # window writes ~9 MB a second
            self.trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host annotations only
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        self.compile.in_window = True

    def end_window(self) -> None:
        self.compile.in_window = False
        self.gc.in_window = False
        self.log(f"[gc] {self.gc.count} collections in the window "
                 f"({self.gc.full} full), "
                 f"longest {self.gc.longest_s * 1e3:.3f} ms, total "
                 f"{self.gc.total_s * 1e3:.3f} ms")
        if self._window_span is not None:
            import jax
            self._window_span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._window_span = None
