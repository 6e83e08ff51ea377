"""What one run carries from set-up to its result line."""

from __future__ import annotations

import contextlib
import os
import time


class SpanLog:
    """The benchmark's own spans: host clock around its calls into each
    layer. With the profiler on, each span is also written into the trace
    (`jax.profiler.TraceAnnotation`) so device gaps can be attributed."""

    def __init__(self):
        self.spans: list = []  # (name, start_s, end_s)
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1))

    def seconds(self, name: str) -> float | None:
        vals = [e - s for n, s, e in self.spans if n == name]
        return sum(vals) if vals else None


class TraceSession:
    """A short profiler window inside the measured window, started and
    stopped by the traffic kind. Off (a no-op) in a ``--trace 0`` run."""

    def __init__(self, enabled: bool, log_dir: str, spans: SpanLog):
        self.enabled, self.log_dir, self.spans = enabled, log_dir, spans
        self.active = False
        self.window_s = 0.0
        self._t0 = 0.0

    def start(self) -> None:
        if not self.enabled or self.active:
            return
        import jax

        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans are the benchmark's own
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.spans.annotate = True
        self.active = True
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self.window_s += time.perf_counter() - self._t0
        self.spans.annotate = False
        self.active = False
        jax.profiler.stop_trace()


class Ctx:
    """One run: the cell's data, the seed, and what the run collects."""

    def __init__(self, **kw):
        self.spec = kw["spec"]
        self.cell = kw["cell"]
        self.config = kw["config"]
        self.traffic = kw["traffic"]
        self.seed = int(kw["seed"])
        self.seconds = float(kw["seconds"])
        self.trace = bool(kw["trace"])
        self.rehearsal = bool(kw.get("rehearsal", False))
        #: the lower-precision control (never set by a benchmark run)
        self.control = bool(kw.get("control", False))
        self.chips = int(self.cell["chips"])
        self.device = kw.get("device")
        self.spans = kw.get("spans") or SpanLog()
        self.events: list = []  # telemetry events of the program
        self.counters: dict = {}
        self.series: dict = {}
        self.tracer = kw.get("tracer")
        self.trace_reduction: dict | None = None
        self.deployment = None
        self.window = None  # (start_s, end_s) on the host clock

    def say(self, what: str, **kv) -> None:
        body = " ".join(f"{k}={v}" for k, v in kv.items())
        print(f"[bench] {what}: {body}", flush=True)
