"""Build records: each program's trace, lowering and compile (or cache read),
its first run, and Python's collections, on the clock of `obs/trace.py`.

Set-up is where a process builds what it will run, and JAX says what it
builds through `jax.monitoring`: the start of each of its three stages (a
scalar event), the end with its interval (a time-span event, stamped with
`time.time()`, converted here once to `time.monotonic`), and whether a
compile was asked of the persistent cache and found there (two events on the
same thread, inside that compile). Installed once, when `alphafold2_tpu.obs`
is imported, and always on: a few listener calls per build, nothing per step
or per fold.

- A stage that starts while another is under way on the same thread (the
  inner jitted functions a trace meets, an `eval_shape` in a model's body)
  is part of it: only a thread's outermost stage is recorded, so nothing is
  summed twice.
- A record names the program it built. Where the caller says so
  (`program(tag)`, which `FoldExecutor` enters around each key's build, or
  `mark(tag)`, which the training step's body calls while it is traced) the
  record is tagged; a trace's tag carries to the lowering and compile of the
  same `jit(<fun_name>)` on the thread, so a wrapper jitted around a marked
  body is tagged too. Anything else is booked untagged, under JAX's name
  for the function.
- `build` counts a program's builds: each `program(tag)` scope is one (an
  eager operation run inside it is part of it), elsewhere each trace begins
  one, and the lowering, compile and first run after it belong to it. A
  second build of the same jitted object hits JAX's in-memory caches: a
  trace record, and no other.
- `first_run(tag)` books a build's first execution as one more stage.
- `gc.callbacks` books each collection of Python's collector (start, pause,
  generation) and enters a `gc` profiler annotation for it. The callback
  takes no lock (a collection can start while its thread holds any); its
  pauses reach the registry on the next record or read.

Registry: `af2_build_seconds_total{program, stage}`,
`af2_builds_total{program, cache}` (one a compile: `hit`, `miss` or `none`
where no persistent cache was asked; untagged builds under the program
`untagged`) and the histogram `af2_gc_pause_seconds{generation}`.
"""

from __future__ import annotations

import contextlib
import gc
import logging
import threading
import time
from collections import deque
from typing import List, Optional

from jax import monitoring
from jax.profiler import TraceAnnotation

from alphafold2_tpu.obs.registry import get_registry

_STAGE_OF = {"/jax/core/compile/jaxpr_trace_duration": "trace",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
             "/jax/core/compile/backend_compile_duration": "compile"}
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# JAX stamps its stages with time.time(): the one conversion to the
# monotonic clock every span of this package uses
_TO_MONOTONIC = time.monotonic() - time.time()

_lock = threading.Lock()
_records: List[dict] = []
_builds: dict = {}                    # program -> builds begun
_local = threading.local()

# (start, pause, generation) of recent collections, and those not yet in
# the registry: appended by the collector's callback alone
_collections: deque = deque(maxlen=1 << 16)
_unbooked: deque = deque(maxlen=1 << 16)
_gc_open: list = []                   # [annotation, start] of the one under way
_book_lock = threading.Lock()


class _Thread:
    """What one thread has under way: its open stages (outermost first), the
    tags `program` entered, and the tag each trace left for its lowering and
    compile (by `jit(<fun_name>)`)."""

    __slots__ = ("open", "programs", "carry")     # programs: (tag, build)

    def __init__(self):
        self.open: List[dict] = []
        self.programs: List[str] = []
        self.carry: dict = {}


def _thread() -> _Thread:
    state = getattr(_local, "state", None)
    if state is None:
        state = _local.state = _Thread()
    return state


# -- what the caller says --------------------------------------------------

@contextlib.contextmanager
def program(tag: str):
    """The scope is one build of `tag`: every stage recorded on this thread
    inside it is that build's."""
    with _lock:
        _builds[tag] = _builds.get(tag, 0) + 1
        build = _builds[tag]
    programs = _thread().programs
    programs.append((tag, build))
    try:
        yield
    finally:
        programs.pop()


def mark(tag: str) -> None:
    """Called from a program's body: the build under way on this thread (the
    outermost, whatever jit wraps the body) is `tag`'s. Nothing when no build
    is under way (the body run eagerly)."""
    frames = _thread().open
    if frames and frames[0]["mark"] is None:
        frames[0]["mark"] = tag


@contextlib.contextmanager
def first_run(tag: str):
    """Books the scope as the first execution of `tag`'s latest build (a
    `first_run` profiler annotation too)."""
    with TraceAnnotation("first_run"):
        start = time.monotonic()
        try:
            yield
        finally:
            _commit({"program": tag, "fun_name": "", "stage": "first_run",
                     "start": start, "end": time.monotonic(),
                     "cache": "none", "tagged": True}, begins=False)


# -- the listeners ---------------------------------------------------------

def _quiet(listener):
    """A listener runs inside JAX's compile path: a fault of the recorder is
    logged, never raised into the build."""
    def call(*args, **kwargs):
        try:
            listener(*args, **kwargs)
        except Exception:
            logging.getLogger(__name__).exception("build record lost")
    return call


@_quiet
def _on_start(event, value, **kwargs):
    stage = _STAGE_OF.get(event)
    if stage is not None:
        _thread().open.append({"stage": stage, "mark": None, "cache": "none"})


@_quiet
def _on_event(event, **kwargs):
    if event not in (_CACHE_ASKED, _CACHE_HIT):
        return
    frames = _thread().open
    if frames and frames[-1]["stage"] == "compile":
        frames[-1]["cache"] = "hit" if event == _CACHE_HIT else "miss"


@_quiet
def _on_span(event, start_time, end_time, fun_name="", **kwargs):
    stage = _STAGE_OF.get(event)
    if stage is None:
        return
    state = _thread()
    frame = {"stage": stage, "mark": None, "cache": "none"}
    if state.open and state.open[-1]["stage"] == stage:
        frame = state.open.pop()
    if state.open:
        return            # inside a stage under way: its time is that one's
    fun_name = str(fun_name)
    record = {"program": fun_name, "fun_name": fun_name, "stage": stage,
              "start": start_time + _TO_MONOTONIC,
              "end": end_time + _TO_MONOTONIC, "cache": frame["cache"],
              "tagged": False}
    if state.programs:
        record["program"], record["build"] = state.programs[-1]
        record["tagged"] = True
    elif frame["mark"] is not None:
        record["program"], record["tagged"] = frame["mark"], True
    else:
        record["program"], record["tagged"] = state.carry.get(
            fun_name, (fun_name, False))
    if stage == "trace":
        state.carry[f"jit({fun_name})"] = (record["program"],
                                           record["tagged"])
    _commit(record, begins=stage == "trace")


def _on_gc(phase, info):
    # no lock, no logging (whose handlers lock): the collection may have
    # started while this thread holds either
    try:
        if phase == "start":
            annotation = TraceAnnotation("gc")
            annotation.__enter__()
            _gc_open[:] = [annotation, time.monotonic()]
        elif _gc_open:
            annotation, start = _gc_open
            pause = time.monotonic() - start
            annotation.__exit__(None, None, None)
            _gc_open.clear()
            entry = (start, pause, info["generation"])
            _collections.append(entry)
            _unbooked.append(entry)
    except Exception:
        pass


# -- booking ---------------------------------------------------------------

def _commit(record: dict, begins: bool) -> None:
    """Books `record` as a stage of its program's latest build, or of a new
    one where it `begins` it (a trace outside `program`)."""
    program_ = record["program"]
    with _lock:
        if "build" not in record:
            if begins or program_ not in _builds:
                _builds[program_] = _builds.get(program_, 0) + 1
            record["build"] = _builds[program_]
        _records.append(record)
    # an untagged build is booked under JAX's name for the function in its
    # record, and as `untagged` here: every eager operation is one
    label = program_ if record["tagged"] else "untagged"
    registry = get_registry()
    registry.counter(
        "af2_build_seconds_total",
        "seconds of each stage of each program's builds (trace, lower, "
        "compile, first_run)", ("program", "stage")).inc(
        max(record["end"] - record["start"], 0.0),
        program=label, stage=record["stage"])
    if record["stage"] == "compile":
        registry.counter(
            "af2_builds_total", "compiles of each program, by what the "
            "persistent cache gave (hit, miss, none)",
            ("program", "cache")).inc(1, program=label,
                                      cache=record["cache"])
    flush()


def flush() -> None:
    """Books the collections since the last call into
    `af2_gc_pause_seconds{generation}`."""
    if not _unbooked:
        return
    with _book_lock:
        pauses = get_registry().histogram(
            "af2_gc_pause_seconds", "pause of each collection of Python's "
            "collector", ("generation",))
        while True:
            try:
                _, pause, generation = _unbooked.popleft()
            except IndexError:
                return
            pauses.observe(pause, generation=generation)


def _copy(entries: deque) -> list:
    """A deque the collector may append to while it is read."""
    while True:
        try:
            return list(entries)
        except RuntimeError:
            continue


def records() -> List[dict]:
    """Every build record so far, in the order they were booked: `program`,
    `fun_name`, `stage` (trace, lower, compile or first_run), `start` and
    `end` (`time.monotonic`), `cache` (hit, miss or none), `tagged` and
    `build` (1 for the program's first)."""
    flush()
    with _lock:
        return [dict(r) for r in _records]


def collections(since: Optional[float] = None) -> List[dict]:
    """The collections booked (the latest 65,536), from `since` on: `start`
    (`time.monotonic`), `pause` in seconds and `generation`."""
    flush()
    return [{"start": start, "pause": pause, "generation": generation}
            for start, pause, generation in _copy(_collections)
            if since is None or start >= since]


monitoring.register_scalar_listener(_on_start)
monitoring.register_event_listener(_on_event)
monitoring.register_event_time_span_listener(_on_span)
gc.callbacks.append(_on_gc)
