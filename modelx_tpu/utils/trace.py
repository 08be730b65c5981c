"""Structured spans for the registry->HBM path (SURVEY.md §5: the reference
has no tracing at all — only per-request wall-clock logging in
pkg/registry/helper.go:98-113).

One primitive, ``span()``, with three sinks:

* a cumulative aggregate per span path (count / total_s / max_s / self_s),
  bounded by the number of distinct paths and never evicted — what
  ``/v1/trace`` serves, so ``serve.load`` is still there after a day of
  traffic;
* a bounded ring of the spans closed under a request context — what
  ``/v1/trace?request_id=`` slices;
* the profiler: while a capture started by :func:`jax_profile` runs, a span
  also opens a ``jax.profiler.TraceAnnotation`` under its path, so it lands
  on the host plane of the same ``.xplane.pb``, on the device trace's clock.
  Outside a capture no jax call is made and this module imports without jax
  (the registry and the client import it).

    with trace.span("dl.load", uri=uri):
        with trace.span("fetch", tensor=name):   # path "dl.load/fetch"
            ...

Two clocks built on the same sinks tile a thread's time instead of nesting
in it: :class:`Phases` (the engine loop: every instant of a step is in
exactly one leaf phase) and :data:`startup` (process creation -> ready, by
stage). Every closed span is logged at DEBUG (INFO with ``MODELX_TRACE=1``,
read once at import).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import logging
import os
import threading
import time
import weakref
from typing import Any, Iterator

from modelx_tpu import T_FIRST_LINE  # the first line any entry point runs

logger = logging.getLogger("modelx.trace")

MAX_SPANS = 8192

_LOG_LEVEL = logging.INFO if os.environ.get("MODELX_TRACE") else logging.DEBUG

# the open span of this thread/task: [path, seconds its closed children took,
# the frame of its parent]
_current: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "modelx_span", default=None)

# the request id (ISSUE 13) rides a contextvar parallel to the span path:
# every span closed while a request context is active carries the id, so
# /v1/trace can filter one request's timeline out of the ring
_current_request: contextvars.ContextVar[str] = contextvars.ContextVar(
    "modelx_request_id", default="")

# Set by jax_profile for as long as its capture runs: jax's TraceAnnotation
# class, else None. Everything that bridges to the profiler tests this one
# name, so outside a capture a span costs no jax call.
_annotate = None

# Spans that last as long as a request are not bridged: the trace reduction
# (benchmark/xplane.py) gives a device-idle gap to the shortest host event
# that covers most of it, else to the one that overlaps it most — an
# envelope overlaps every gap and would take them all.
_ENVELOPES = ("serve.request", "serve.generate", "router.request")


def current_request_id() -> str:
    """The request id bound to this thread/task context ("" when none)."""
    return _current_request.get()


@contextlib.contextmanager
def request_context(request_id: str) -> Iterator[None]:
    """Bind a request id for the duration of a block: every span closed
    inside (across nested calls, same thread/task) is stamped with it."""
    token = _current_request.set(str(request_id or ""))
    try:
        yield
    finally:
        _current_request.reset(token)


def _fold(agg: dict[str, list], path: str, n: int, total: float, longest: float,
          own: float) -> None:
    """Add to ``agg[path]`` = [count, total_s, max_s, self_s]."""
    a = agg.get(path)
    if a is None:
        a = agg[path] = [0, 0.0, 0.0, 0.0]
    a[0] += n
    a[1] += total
    if longest > a[2]:
        a[2] = longest
    a[3] += own


class Tracer:
    """The aggregate (every span, never evicted) and the ring (spans of
    requests, bounded; the drop count is tracked)."""

    def __init__(self, max_spans: int = MAX_SPANS) -> None:
        self._lock = threading.Lock()
        self._spans: collections.deque[dict[str, Any]] = collections.deque(maxlen=max_spans)
        self._agg: dict[str, list] = {}  # path -> [count, total_s, max_s, self_s]
        self._clocks: "weakref.WeakSet[Phases]" = weakref.WeakSet()
        self._dropped = 0
        self.max_spans = max_spans

    def record(self, span: dict[str, Any]) -> None:
        dur = span["duration_s"]
        with self._lock:
            _fold(self._agg, span["path"], 1, dur, dur, span.get("self_s", dur))
            if "request_id" in span:
                if len(self._spans) == self.max_spans:
                    self._dropped += 1  # deque(maxlen) evicts the oldest in O(1)
                self._spans.append(span)
        if startup.collecting:
            startup.keep(span)
        if logger.isEnabledFor(_LOG_LEVEL):
            logger.log(
                _LOG_LEVEL,
                "span %s %.1fms %s",
                span["path"],
                dur * 1e3,
                {k: v for k, v in span.items() if k not in ("path", "start_s", "duration_s")},
            )

    def spans(self, prefix: str = "",
              request_id: str = "") -> list[dict[str, Any]]:
        """The ring: spans closed under a request context, oldest first."""
        # one O(n) copy under the lock, filtering OUTSIDE it: concurrent
        # record() calls never wait on a caller's aggregation
        with self._lock:
            out = list(self._spans)
        if prefix:
            out = [s for s in out if s["path"].startswith(prefix)]
        if request_id:
            out = [s for s in out if s.get("request_id") == request_id]
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._agg.clear()
            self._dropped = 0

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def summary(self, prefix: str = "",
                request_id: str = "") -> dict[str, dict[str, float]]:
        """Per-path count / total_s / max_s / self_s (for /metrics and
        /v1/trace). Without ``request_id``: the cumulative aggregate since
        the process started, phase clocks included. With it: that request's
        spans, aggregated from the ring."""
        agg: dict[str, list] = {}
        if request_id:
            for s in self.spans(prefix, request_id):
                dur = s["duration_s"]
                _fold(agg, s["path"], 1, dur, dur, s.get("self_s", dur))
        else:
            with self._lock:
                agg = {p: list(a) for p, a in self._agg.items()}
                clocks = list(self._clocks)
            for clock in clocks:
                for path, row in clock.aggregate().items():
                    _fold(agg, path, *row)
            if prefix:
                agg = {p: a for p, a in agg.items() if p.startswith(prefix)}
        return {p: {"count": a[0], "total_s": a[1], "max_s": a[2], "self_s": a[3]}
                for p, a in agg.items()}


_tracer = Tracer()


def tracer() -> Tracer:
    return _tracer


def record(path: str, start_s: float, duration_s: float, **attrs: Any) -> None:
    """A span stamped after the fact (its caller kept the clock)."""
    rid = _current_request.get()
    if rid:
        attrs["request_id"] = rid
    _tracer.record({**attrs, "path": path, "start_s": start_s, "duration_s": duration_s})


class span:
    """Time a block; ``with span(...) as rec`` yields a dict that accepts
    extra attrs while open. Paths nest per thread/task (``parent/name``)."""

    __slots__ = ("name", "rec", "_frame", "_token", "_start", "_ann")

    def __init__(self, name: str, **attrs: Any) -> None:
        self.name = name
        self.rec: dict[str, Any] = attrs

    def __enter__(self) -> dict[str, Any]:
        parent = _current.get()
        self._frame = [f"{parent[0]}/{self.name}" if parent else self.name, 0.0, parent]
        self._token = _current.set(self._frame)
        self._ann = None
        if _annotate is not None and not self.name.startswith(_ENVELOPES):
            self._ann = _annotate(self._frame[0])
            self._ann.__enter__()
        self._start = time.monotonic()
        return self.rec

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = time.monotonic() - self._start
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        try:
            _current.reset(self._token)
        except ValueError:
            # an abandoned generator's span, closed by the collector in
            # another thread's context: there is nothing to restore there
            pass
        path, child_s, parent = self._frame
        if parent is not None:
            parent[1] += dur
        rec = self.rec
        if exc is not None:
            rec["error"] = f"{type(exc).__name__}: {exc}"
        rec["path"] = path
        rec["start_s"] = self._start
        rec["duration_s"] = dur
        rec["self_s"] = max(0.0, dur - child_s)
        rid = _current_request.get()
        if rid:
            rec["request_id"] = rid
        _tracer.record(rec)


class Phases:
    """Leaf phases that TILE one thread's loop: between ``begin`` and ``end``
    every instant belongs to exactly one phase, so the phase seconds sum to
    the loop's wall time by construction. ``begin(i, step_num)`` opens a step
    (closing the one before) in phase ``i``; ``to(i)`` switches phase with
    one clock read and no allocation. Per step, the thread's CPU time
    (``time.thread_time``) is added up beside the wall time: over the phases
    that do not wait, wall minus CPU is the time the thread stood by for
    the GIL or the scheduler. During a profiler capture the step and each
    phase are also annotations (``<step>`` with ``step_num``, and
    ``<step>/<phase>``); phases never enter the ring."""

    def __init__(self, step: str, names: tuple[str, ...]) -> None:
        self.step = step
        self.names = names
        self.paths = tuple(f"{step}/{n}" for n in names)
        self.seconds = [0.0] * len(names)
        self.entries = [0] * len(names)
        self.longest = [0.0] * len(names)
        self.steps = 0
        self.wall_s = 0.0
        self.cpu_s = 0.0
        self._cur = -1
        self._t = self._t_step = self._cpu_step = 0.0
        self._ann = self._ann_step = None
        _tracer._clocks.add(self)

    def _close(self, now: float) -> None:
        dt = now - self._t
        self.seconds[self._cur] += dt
        if dt > self.longest[self._cur]:
            self.longest[self._cur] = dt
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def _open(self, i: int, now: float) -> None:
        self._cur = i
        self._t = now
        self.entries[i] += 1
        if _annotate is not None:
            self._ann = _annotate(self.paths[i])
            self._ann.__enter__()

    def to(self, i: int) -> None:
        if i == self._cur or self._cur < 0:  # no entry for staying; no step open
            return
        now = time.monotonic()
        self._close(now)
        self._open(i, now)

    def begin(self, i: int, step_num: int = 0) -> None:
        self.end()
        now = time.monotonic()
        self._t_step = now
        self._cpu_step = time.thread_time()
        self.steps += 1
        if _annotate is not None:
            self._ann_step = _annotate(self.step, step_num=step_num)
            self._ann_step.__enter__()
        self._open(i, now)

    def end(self) -> None:
        if self._cur < 0:
            return
        now = time.monotonic()
        self._close(now)
        self._cur = -1
        self.wall_s += now - self._t_step
        self.cpu_s += time.thread_time() - self._cpu_step
        if self._ann_step is not None:
            self._ann_step.__exit__(None, None, None)
            self._ann_step = None

    def aggregate(self) -> dict[str, tuple[int, float, float, float]]:
        """path -> (entries, seconds, longest, self seconds): a phase is a
        leaf, and the step is all phases."""
        out = {p: (n, s, m, s) for p, n, s, m in
               zip(self.paths, self.entries, self.seconds, self.longest) if n}
        if self.steps:
            out[self.step] = (self.steps, self.wall_s, 0.0, 0.0)
        return out


def _process_age_s() -> float | None:
    """Seconds since the kernel created this process: its start time in
    ``/proc/self/stat`` (clock ticks since boot) against the boot clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class Startup:
    """Process creation -> the first token, by stage. Like :class:`Phases`
    the stages tile: ``begin(name)`` closes ``imports`` (interpreter start and
    imports, from the process's creation), each ``stage(name)`` closes the one
    before, and ``ready()`` closes the last, so the stage seconds sum to
    ``ready_s``. A stage tiles one level down the same way: ``sub(name)``
    closes the sub-stage before it — the first one of a stage began with the
    stage, the last one ends with it — so a stage's sub-stages sum to the
    stage (``<stage>_<sub>_s``). Past ready the tiling goes on with two stages
    outside ``ready_s``, closed once, by the first request that yields a
    token (``first_token``): ``first_wait`` (ready -> that request's arrival)
    and ``first_request`` (arrival -> its first token); ``first_token_s`` is
    process creation -> that token. Each closed stage and sub-stage is also
    a span, ``startup.<stage>`` and ``startup.<stage>/<sub>``. ``note`` keeps
    what falls outside the tiling (an engine built lazily by the first
    request): its seconds, and when it ended.

    From ``begin`` to the first token the clock also keeps every span the
    process closes (:meth:`timeline`): one list, bounded, frozen at the
    first token — the start as one reads it afterwards, which neither the
    aggregate (no order) nor the ring (requests only) can give."""

    MAX_TIMELINE = 4096
    # reads and puts shorter than this are merged into one entry a path
    MERGE_BELOW_S = 1e-3
    _MERGED = ("dl.fetch", "dl.put")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        self.t0: float | None = None
        self.source = ""
        self._stages: dict[str, float] = {}
        self._subs: dict[str, float] = {}  # "<stage>_<sub>" -> seconds
        self._extra: dict[str, float] = {}
        self._cur = ""
        self._t = 0.0
        self._sub = ""  # the open sub-stage of the open stage, and its start
        self._t_sub = 0.0
        self._owner = 0  # the thread that opened the stage: only it splits it
        self.ready_s: float | None = None
        self.first_token_s: float | None = None
        self.collecting = False  # begin -> first token: spans go to the timeline
        self._timeline: list[dict[str, Any]] = []
        self._merged: dict[str, dict[str, Any]] = {}
        self.timeline_dropped = 0

    def begin(self, stage: str) -> None:
        now, first = time.monotonic(), T_FIRST_LINE
        age = _process_age_s()
        with self._lock:
            if self.t0 is not None:
                return
            # the kernel's stamp has the clock tick's resolution (10 ms);
            # one that says the process is younger than its own first line
            # is wrong (a sandboxed /proc)
            if age is not None and age >= now - first - 0.02:
                self.t0, self.source = now - age, "proc_stat"
            else:
                self.t0, self.source = first, "first_line"
            self._cur, self._t = "imports", self.t0
            # process creation -> the package's first line -> here
            self._subs["imports_interpreter"] = first - self.t0
            self._sub, self._t_sub = "modules", first
            self._owner = threading.get_ident()
            self.collecting = True
        record("startup.imports/interpreter", self.t0, first - self.t0)
        self.stage(stage)

    def _close_sub(self, now: float) -> tuple | None:
        """Under the lock: close the open sub-stage at ``now``."""
        if not self._sub:
            return None
        key, start = f"{self._cur}_{self._sub}", self._t_sub
        self._subs[key] = self._subs.get(key, 0.0) + now - start
        closed = (f"startup.{self._cur}/{self._sub}", start, now - start)
        self._sub = ""
        return closed

    def stage(self, name: str) -> None:
        now = time.monotonic()
        with self._lock:
            if self.t0 is None or self.ready_s is not None:
                return
            sub = self._close_sub(now)
            prev, start = self._cur, self._t
            self._stages[prev] = self._stages.get(prev, 0.0) + now - start
            self._cur, self._t = name, now
            self._owner = threading.get_ident()
        if sub is not None:
            record(*sub)
        record(f"startup.{prev}", start, now - start)

    def sub(self, name: str) -> None:
        """The open stage goes on in sub-stage ``name``: one clock read.
        Only for the thread that opened the stage (loads side by side on
        threads of their own would not tile), and not past ready."""
        now = time.monotonic()
        with self._lock:
            if (self.t0 is None or self.ready_s is not None or name == self._sub
                    or threading.get_ident() != self._owner):
                return
            closed = self._close_sub(now)
            # a stage's first sub-stage began with the stage
            self._sub, self._t_sub = name, (now if closed else self._t)
        if closed is not None:
            record(*closed)

    def ready(self) -> None:
        self.stage("")
        with self._lock:
            if self.t0 is not None and self.ready_s is None:
                self.ready_s = self._t - self.t0

    def first_token(self, arrived: float) -> None:
        """The first request to yield a token, which arrived at ``arrived``
        (``time.monotonic``), has it now: closes ``first_wait`` and
        ``first_request``, once, and freezes the timeline."""
        now = time.monotonic()
        with self._lock:
            if self.ready_s is None or self.first_token_s is not None:
                return
            t_ready = self.t0 + self.ready_s
            arrived = min(max(arrived, t_ready), now)
            self._stages["first_wait"] = arrived - t_ready
            self._stages["first_request"] = now - arrived
            self.first_token_s = now - self.t0
        record("startup.first_wait", t_ready, arrived - t_ready)
        record("startup.first_request", arrived, now - arrived)
        self.collecting = False

    def keep(self, span: dict[str, Any]) -> None:
        """A span closed while ``collecting``: one entry of the timeline."""
        path, dur = span["path"], span["duration_s"]
        with self._lock:
            if self.t0 is None:
                return
            short = dur < self.MERGE_BELOW_S and path.endswith(self._MERGED)
            entry = self._merged.get(path) if short else None
            if entry is not None:
                entry["duration_s"] += dur
                entry["attrs"]["merged"] += 1
                entry["attrs"]["bytes"] += span.get("bytes", 0)
                return
            if len(self._timeline) >= self.MAX_TIMELINE:
                self.timeline_dropped += 1
                return
            attrs = {k: v if isinstance(v, (int, float, str, bool)) else str(v)
                     for k, v in span.items()
                     if k not in ("path", "start_s", "duration_s", "self_s")}
            entry = {"path": path, "at_s": span["start_s"] - self.t0, "duration_s": dur,
                     "thread": threading.current_thread().name, "attrs": attrs}
            if short:
                attrs.update(merged=1, bytes=span.get("bytes", 0))
                self._merged[path] = entry
            self._timeline.append(entry)

    def timeline(self) -> dict:
        """``{spans: [...], dropped, frozen}``: the spans closed from process
        creation to the first token, in start order, each with ``at_s``
        (seconds since process creation), ``duration_s``, the thread's name
        and its attributes."""
        with self._lock:
            spans = [dict(e, at_s=round(e["at_s"], 6), duration_s=round(e["duration_s"], 6),
                          attrs=dict(e["attrs"])) for e in self._timeline]
            dropped, frozen = self.timeline_dropped, self.first_token_s is not None
        spans.sort(key=lambda e: e["at_s"])
        return {"spans": spans, "dropped": dropped, "frozen": frozen}

    def note(self, name: str, seconds: float) -> None:
        """``seconds`` of ``name`` just ended: added up under ``<name>_s``,
        and the first such instant kept under ``<name>_at_s``."""
        with self._lock:
            if self.t0 is not None:
                self._extra[f"{name}_s"] = self._extra.get(f"{name}_s", 0.0) + seconds
                self._extra.setdefault(f"{name}_at_s", time.monotonic() - self.t0)

    def count(self, name: str, n: int) -> None:
        """``n`` more of ``name`` (a count beside the stages, under its own
        name)."""
        with self._lock:
            if self.t0 is not None:
                self._extra[name] = self._extra.get(name, 0) + n

    def snapshot(self) -> dict:
        """``{<stage>_s..., <stage>_<sub>_s..., ready_s, first_token_s,
        <noted>_s..., <instant>_at_s..., source}``; empty in a process that
        never called ``begin``."""
        with self._lock:
            if self.t0 is None:
                return {}
            out = {f"{k}_s": round(v, 4) for k, v in (*self._stages.items(),
                                                      *self._subs.items())}
            out.update({k: round(v, 4) for k, v in self._extra.items()})
            if self.ready_s is not None:
                out["ready_s"] = round(self.ready_s, 4)
            if self.first_token_s is not None:
                out["first_token_s"] = round(self.first_token_s, 4)
            out["source"] = self.source
            return out


startup = Startup()


@contextlib.contextmanager
def jax_profile(trace_dir: str, python_tracer: bool = False) -> Iterator[None]:
    """Device-level profiling window (jax.profiler trace, viewable in
    tensorboard/xprof), with spans and phases bridged into it. The python
    tracer is off unless asked for: its per-call hooks tripled the host work
    they were meant to time (a first request of 13.8 s read 36.7 s). jax's
    own TraceMe events (``backend_compile``, ``PjitFunction(...)``) are
    host-tracer events and stay. No-op if jax is unavailable."""
    global _annotate
    try:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 1 if python_tracer else 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        _annotate = jax.profiler.TraceAnnotation
        started = True
    except Exception as e:  # profiling must never take the service down
        logger.warning("jax profiler unavailable: %s", e)
        started = False
    try:
        yield
    finally:
        if started:
            _annotate = None
            try:
                jax.profiler.stop_trace()
            except Exception as e:
                logger.warning("jax profiler stop failed: %s", e)
