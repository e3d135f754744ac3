"""paddle.profiler facade over jax.profiler (reference:
python/paddle/profiler/profiler.py, C++ host/device tracers under
paddle/fluid/platform/profiler/ — unverified, SURVEY.md §0/§5).

The reference's CUPTI device tracer + chrome-trace exporter maps to XLA's
XPlane tracing: ``Profiler`` drives ``jax.profiler.start_trace`` /
``stop_trace`` (TensorBoard-loadable), ``RecordEvent`` maps to
``jax.profiler.TraceAnnotation``, and scheduler windows are honored by
step counting in ``step()``.

``RecordEvent`` is also the program's ONE host-span call: besides the
annotation (which any profiler session shows on the device trace's
clock) it appends a row to the process's one
:class:`~paddle_tpu.obs.trace.TraceRecorder`, always, so the spans read
the same with and without a profiler session. The serving pump and the
train dispatch are spanned with it (``PERF.md`` section 3 has the
table), and :func:`count_compile_events` charges JAX's compile events
to the step span that was open when they happened.
"""
from __future__ import annotations

import enum
import os
import threading
import time

import jax

from ..obs.registry import MetricsRegistry
from ..obs.trace import TraceRecorder
from .mfu import MFUMeter, transformer_train_flops, peak_flops_per_chip  # noqa: F401

__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
    "MFUMeter", "transformer_train_flops", "peak_flops_per_chip",
]


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1
    XPU = 2
    CUSTOM_DEVICE = 3
    TPU = 4


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Returns a callable mapping step number → ProfilerState (paddle
    parity; window boundaries drive trace start/stop)."""
    period = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    """Returns an on_trace_ready callback storing traces under dir_name
    (jax writes TensorBoard/XPlane format; pass the same dir to
    TensorBoard's profile plugin)."""

    def handler(prof):
        prof._export_dir = dir_name

    return handler


def load_profiler_result(path, mesh_axes=None):
    """Read a saved device trace (a ``.xplane.pb`` or a profiler log
    directory; the newest trace in it) into a
    :class:`~paddle_tpu.profiler.reader.ProfilerResult`: device seconds
    by program and named scope, collectives by mesh axis (``mesh_axes``:
    ``parallel.mesh.axis_groups()`` of the mesh that ran, default the
    installed one), idle gaps by ``RecordEvent`` span. ``print`` it for
    the tables; ``python -m paddle_tpu.obs profile --in <dir>`` does."""
    from . import reader  # lazily: the engine's import does not need it
    from ..parallel import mesh as mesh_state

    if mesh_axes is None and mesh_state.has_mesh():
        mesh_axes = mesh_state.axis_groups()
    return reader.load(path, mesh_axes=mesh_axes)


# the step-level rows, which carry ``cpu_s``
_CPU_ROWS = frozenset({"door.pump", "engine.step", "train.run_steps"})

# .stack: this thread's open RecordEvents; .cache_load: seconds of a
# persistent-cache load whose backend-compile event is still to come
_open = threading.local()


class RecordEvent:
    """Context manager annotating a host region (reference:
    paddle.profiler.RecordEvent). One call does two things: it enters a
    ``jax.profiler.TraceAnnotation(name)``, so a profiler session shows
    the region on the XLA trace timeline, and on exit it appends one row
    to the process's :class:`~paddle_tpu.obs.trace.TraceRecorder`: name,
    start and end on ``time.perf_counter`` (``t0`` / ``t1``, readable by
    the caller), the span open on this thread when it began (its
    parent), and ``args`` — the identifiers handed in (``req_id``,
    ``step``, ...), which the caller may add to while the span is open.
    ``step_kind`` marks the span as one STEP of the program (the engine
    and the trainer name theirs: mixed, decode, spec_round, train):
    JAX's compile events are charged to the outermost such span open on
    the thread (:func:`count_compile_events`). The step-level rows
    (``door.pump``, ``engine.step``, ``train.run_steps``) also carry
    ``cpu_s``, this thread's CPU seconds over the span
    (``time.thread_time``): a slow row whose ``cpu_s`` is small was off
    the CPU (descheduled, or blocked in the runtime), one whose ``cpu_s``
    is its duration was the interpreter. Host code only: never inside a
    jitted body."""

    __slots__ = ("name", "args", "step_kind", "id", "parent", "t0", "t1",
                 "_ann", "_stack", "_cpu0")

    def __init__(self, name, event_type=None, *, step_kind=None, **ids):
        self.name = name
        self.args = ids
        self.step_kind = step_kind
        self.id = self.parent = self.t0 = self.t1 = None
        self._ann = self._stack = self._cpu0 = None

    def begin(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.id = TraceRecorder.process().next_id()
        self.parent = stack[-1].id if stack else None
        self._stack = stack  # its thread's open spans: end() leaves these
        stack.append(self)
        if self.name in _CPU_ROWS:
            self._cpu0 = time.thread_time()
        # nothing between the annotation and the stamp: the two clocks'
        # durations are held within a millisecond of each other
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def end(self):
        if self._ann is None:
            return
        self.t1 = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self._ann = None
        if self._cpu0 is not None:
            self.args["cpu_s"] = time.thread_time() - self._cpu0
        stack = self._stack
        if stack[-1] is self:
            stack.pop()
        else:  # ended out of order, or on another thread than it began on
            stack.remove(self)
        TraceRecorder.process().span(self.name, self.t0, self.t1, self.id,
                                     self.parent, self.args)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
_compile_counters = None


def _step_span():
    """The outermost open span on this thread that its caller marked as
    a step, and its kind: what a compile event is charged to."""
    for span in getattr(_open, "stack", ()):
        if span.step_kind is not None:
            return span, span.step_kind
    return None, "none"


def _bump(span, key, amount=1):
    if span is not None:
        span.args[key] = span.args.get(key, 0) + amount


def count_compile_events(registry=None):
    """Listen to ``jax.monitoring`` (once per process; the first engine
    or train step built calls this, ``import paddle_tpu`` does not) and
    count JAX's compile events into the process's registry
    (``MetricsRegistry.process()``), labelled by the outermost span open
    on the calling thread that was given a ``step_kind`` (``step`` =
    that kind, as the engine and the trainer name theirs: mixed | decode
    | spec_round | train; ``none`` outside any):

    - ``jax_compile_requests_total{step}``: executables JAX asked its
      backend for (each ends in a persistent-cache load or a compile;
      the in-memory jit cache never gets this far),
    - ``jax_compile_cache_hits_total{step}`` /
      ``jax_compile_cache_misses_total{step}``: the persistent cache's,
    - ``jax_compile_seconds_total{stage,step}``: ``stage`` = trace |
      lower | backend | cache_load, disjoint: JAX's backend-compile
      event spans the cache lookup too, so a request's ``cache_load``
      seconds are taken off its ``backend`` seconds here.

    Each event is also added to that step span's own row
    (``compile_requests``, ``compile_cache_hits``,
    ``compile_cache_misses``, ``compile_<stage>_s``). With ``registry``
    the same counters are shown by it too (an engine's ``/metrics``)."""
    global _compile_counters
    if _compile_counters is None:
        proc = MetricsRegistry.process()
        requests = proc.counter(
            "jax_compile_requests_total",
            "executables JAX asked for (cache load or backend compile), "
            "by the step that caused them")
        hits = proc.counter(
            "jax_compile_cache_hits_total",
            "persistent compile cache hits, by step")
        misses = proc.counter(
            "jax_compile_cache_misses_total",
            "persistent compile cache misses, by step")
        seconds = proc.counter(
            "jax_compile_seconds_total",
            "seconds JAX spent tracing, lowering, compiling and loading "
            "from the cache, by stage and step")

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                span, step = _step_span()
                hits.inc(step=step)
                _bump(span, "compile_cache_hits")
            elif event == "/jax/compilation_cache/cache_misses":
                span, step = _step_span()
                misses.inc(step=step)
                _bump(span, "compile_cache_misses")

        def on_duration(event, secs, **_):
            stage = _STAGE_OF.get(event)
            if stage is None:
                return
            span, step = _step_span()
            if stage == "cache_load":
                # raised inside the backend event that follows on this
                # thread: remembered until then
                _open.cache_load = secs
            elif stage == "backend":
                requests.inc(step=step)
                _bump(span, "compile_requests")
                secs -= getattr(_open, "cache_load", 0.0)
                _open.cache_load = 0.0
            secs = max(secs, 0.0)
            seconds.inc(secs, stage=stage, step=step)
            _bump(span, f"compile_{stage}_s", secs)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        _compile_counters = (requests, hits, misses, seconds)
    if registry is not None:
        for c in _compile_counters:
            registry.share(c)
    return _compile_counters


class Profiler:
    """paddle.profiler.Profiler parity on jax.profiler.

    Usage (paddle idiom)::

        p = Profiler(targets=[ProfilerTarget.TPU], scheduler=(2, 5))
        p.start()
        for it, batch in enumerate(loader):
            train_step(batch)
            p.step()
        p.stop()
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, log_dir=None, registry=None):
        # optional paddle_tpu.obs.MetricsRegistry: step() feeds the
        # `profiler_step_seconds` histogram so profiler windows and the
        # serving/train telemetry share one scrape surface
        self._registry = registry
        self._h_step = (registry.histogram(
            "profiler_step_seconds", "Profiler.step() intervals")
            if registry is not None else None)
        if isinstance(scheduler, tuple):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=start, ready=0, record=end - start, repeat=1)
        elif scheduler is None:
            self._scheduler = None  # trace from start() to stop()
        else:
            self._scheduler = scheduler
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._export_dir = log_dir or os.environ.get(
            "PADDLE_PROFILER_LOG_DIR", "/tmp/paddle_tpu_profile")
        if on_trace_ready is not None:
            on_trace_ready(self)
        self._step_no = 0
        self._tracing = self._traced = False
        self._step_times = []
        self._last_step_t = None

    def _maybe_transition(self):
        if self._timer_only:
            return
        if self._scheduler is None:
            want = True
        else:
            want = self._scheduler(self._step_no) in (
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        if want and not self._tracing:
            jax.profiler.start_trace(self._export_dir)
            self._tracing = self._traced = True
        elif not want and self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def start(self):
        self._last_step_t = time.perf_counter()
        self._maybe_transition()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
            if self._h_step is not None:
                self._h_step.observe(now - self._last_step_t)
        self._last_step_t = now
        self._step_no += 1
        self._maybe_transition()

    def stop(self):
        if self._tracing:
            jax.profiler.stop_trace()
            self._tracing = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def step_times(self):
        return list(self._step_times)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """The step-time line, then (after :meth:`stop`, when a trace was
        taken) the tables of :func:`load_profiler_result` over it:
        programs, scopes, collectives, idle gaps. Returns the text."""
        times = self._step_times or [0.0]
        avg = sum(times) / len(times)
        text = (f"steps: {len(times)}  avg: {avg * 1e3:.2f} ms  "
                f"min: {min(times) * 1e3:.2f} ms  max: {max(times) * 1e3:.2f} ms")
        if self._traced and not self._tracing:
            try:
                text += "\n" + load_profiler_result(self._export_dir).tables(
                    top_ops=4 if op_detail else 0, time_unit=time_unit)
            except ValueError as e:  # a CPU trace has no device plane
                text += f"\n(no device tables: {e})"
        return text
