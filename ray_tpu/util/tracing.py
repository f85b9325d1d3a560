"""Distributed task tracing — OpenTelemetry-style spans without the SDK.

Role-equivalent of the reference's opt-in OTel integration
(python/ray/util/tracing/tracing_helper.py, SURVEY §5.1): when
``RAY_TPU_tracing_enabled=1``, the whole task lifecycle is wrapped in a
causally-linked span tree whose context (trace_id, span_id) propagates
inside the TaskSpec, actor-call frames, and Serve proxy metadata — a
driver's ``submit`` span becomes the parent of the controller's
``lease_wait``, the agent's ``worker_start`` and the worker's
``fetch_args``/``execute``/``put_result`` spans, across processes.

Span taxonomy (see docs/observability.md for the full table):

  submit <name>      driver   f.remote() / actor.m.remote() client side
  lease_wait         ctrl     time a lease request sat parked for capacity
  worker_start       agent    cold worker spawn forced by a lease
  fetch_args         worker   dependency resolution before user code
  execute <name>     worker   the user function / actor method body
  put_result         worker   serializing + seeding return values
  queue_wait         worker   in-actor time between arrival and execution
  object_pull/push   any      object-store transfers (bytes attribute)
  collective.<op>    worker   allreduce/… (bytes + world_size attributes)
  serve.request      proxy    HTTP request as seen by the Serve proxy
  serve.replica      replica  replica-side handling of one request

Those are per task, per collective or per request and gated by
``enabled()``. A span opened with ``lifecycle=True`` is recorded whenever
an export directory is known, enabled or not: the dozen once-a-run spans
from a process's boot (``driver.boot``, ``worker.boot``) and
``ray_tpu.init`` to a train worker's first ``train.report`` (and its
programs' ``jax.trace`` / ``jax.lower`` / ``jax.compile`` spans up to
there, three a program), which say where the time to the first step went. After the last of them the flusher thread exits, so a
run with tracing off pays nothing in its steady state.

The exporter is a per-process JSONL file under
``<session_dir>/tracing/spans-<pid>.jsonl`` (the OTel span JSON shape:
name, trace_id, span_id, parent_id, start/end unix-nanos, status,
attributes). Writes are buffered and flushed in batches (size- and
age-triggered, plus atexit) so tracing is not one open()+write() syscall
pair per span. No opentelemetry dependency: the wire model is small
enough to own, and an environment with the SDK installed can lift these
records into any OTLP pipeline verbatim.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import contextvars
import glob
import itertools
import json
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Iterator

from ray_tpu._private.config import global_config

_current: contextvars.ContextVar[tuple[str, str] | None] = contextvars.ContextVar(
    "raytpu_trace_ctx", default=None
)
_lock = threading.Lock()       # guards _flusher_started
_io_lock = threading.Lock()    # serializes file appends
_dir: str | None = None

# Buffered exporter: Span OBJECTS accumulate in a deque (append is
# atomic — no lock on the record path) and are serialized + appended in
# one batch by the flusher thread (age tick / atexit), so the hot path
# pays neither json.dumps nor a write() syscall nor a lock round-trip.
# Small per-task costs here are amplified by GIL contention with the io
# loop thread, so the record path must stay at "a few attribute stores
# and a deque append". The size cap is a memory backstop only — at
# steady state the 0.2s tick drains first.
_BUFFER_SPANS = 8192
_FLUSH_AGE_S = 0.2
# The flusher exits after this many ticks with nothing to write (a run
# whose only spans are the lifecycle ones must not keep a thread waking
# five times a second for good); the next span starts a new one.
_IDLE_TICKS = 3
_buffer: collections.deque = collections.deque()
_flusher_started = False
_atexit_registered = False

# Cheap span/trace ids: one urandom() per process (fork-safe via the pid
# key) + a counter, instead of two urandom syscalls per span. Same hex
# shapes as OTel ids: 16 chars for span_id, 32 for trace_id.
# _id_state = (pid, trace_prefix_16chars, span_prefix_8chars).
_id_state: tuple[int, str, str] | None = None
_id_counter = itertools.count(1)


def _id_prefixes() -> tuple[int, str, str]:
    global _id_state, _id_counter
    state = _id_state
    if state is None or state[0] != os.getpid():
        prefix = os.urandom(8).hex()
        state = _id_state = (os.getpid(), prefix, prefix[:8])
        _id_counter = itertools.count(1)
    return state


def _new_span_id() -> str:
    return f"{_id_prefixes()[2]}{next(_id_counter) & 0xFFFFFFFF:08x}"


def _new_trace_id() -> str:
    return f"{_id_prefixes()[1]}{next(_id_counter) & 0xFFFFFFFFFFFFFFFF:016x}"


def enabled() -> bool:
    return bool(getattr(global_config(), "tracing_enabled", False))


def configure(session_dir: str | None) -> None:
    """Set the export directory (driver: from init; workers: from env)."""
    global _dir
    if session_dir:
        # Drain any buffered spans into the PREVIOUS session's files so a
        # reconfigure (new init in the same process) never leaks old spans
        # into the new session dir.
        try:
            flush()
        except Exception:  # rtlint: disable=swallowed-exception - flush into a dead previous session is best-effort
            pass
        _dir = os.path.join(session_dir, "tracing")


def _export_dir() -> str | None:
    # Memoize the env fallback (workers learn the session dir from the
    # environment): _record() runs per span and must not re-do an environ
    # lookup + path join each time.
    global _dir
    if _dir is None and "RAYTPU_SESSION_DIR" in os.environ:
        _dir = os.path.join(os.environ["RAYTPU_SESSION_DIR"], "tracing")
    return _dir


def _export_path() -> str | None:
    base = _export_dir()
    if base is None:
        return None
    os.makedirs(base, exist_ok=True)
    return os.path.join(base, f"spans-{os.getpid()}.jsonl")


@dataclass(slots=True)
class Span:
    name: str
    trace_id: str
    span_id: str
    parent_id: str | None = None
    start_ns: int = 0
    end_ns: int = 0
    status: str = "ok"
    attributes: dict = field(default_factory=dict)

    def set_error(self, exc: BaseException | str) -> None:
        """Mark the span failed, recording the exception type."""
        self.status = "error"
        if isinstance(exc, BaseException):
            self.attributes["error_type"] = type(exc).__name__
            self.attributes.setdefault("error_message", str(exc)[:200])
        else:
            self.attributes["error_type"] = str(exc)

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start_ns": self.start_ns,
            "end_ns": self.end_ns,
            "status": self.status,
            "pid": _id_state[0] if _id_state else os.getpid(),
            "attributes": self.attributes,
        }


def flush() -> None:
    """Serialize + write every buffered span to the per-process file."""
    if not _buffer:
        return
    batch = []
    while True:
        try:
            batch.append(_buffer.popleft())
        except IndexError:
            break
    if not batch:
        return
    path = _export_path()
    if path is None:
        return
    # Hand-rolled JSON line: every field except name/attributes is an int
    # or hex id we generated, so json.dumps only runs on the two fields
    # that need escaping. ~2x faster than dumps(to_json()) per span, and
    # serialization time steals GIL slices from task execution even on
    # the flusher thread.
    pid = _id_state[0] if _id_state else os.getpid()
    dumps = json.dumps
    parts = []
    for rec in batch:
        parent = '"' + rec.parent_id + '"' if rec.parent_id else "null"
        parts.append(
            f'{{"name":{dumps(rec.name)},"trace_id":"{rec.trace_id}",'
            f'"span_id":"{rec.span_id}","parent_id":{parent},'
            f'"start_ns":{rec.start_ns},"end_ns":{rec.end_ns},'
            f'"status":"{rec.status}","pid":{pid},'
            f'"attributes":{dumps(rec.attributes, separators=(",", ":"))}}}\n'
        )
    lines = "".join(parts)
    with _io_lock:
        # rtlint: disable=blocking-in-async - flush normally runs on the background _flush_loop thread; the async-reachable path is the bounded force-flush at span shutdown
        with open(path, "a") as fh:
            fh.write(lines)


def _flush_loop() -> None:
    global _flusher_started
    idle = 0
    while True:
        time.sleep(_FLUSH_AGE_S)
        idle = 0 if _buffer else idle + 1
        try:
            flush()
        except Exception:
            # Keep the daemon alive; surface persistent write failures
            # when span-level debugging is on.
            logging.getLogger(__name__).debug(
                "trace flush failed", exc_info=True
            )
        if idle >= _IDLE_TICKS:
            # _record appends BEFORE it reads the flag, so a span that
            # read "started" is in the buffer by the time it is looked at
            # here, and one that reads "not started" starts a new thread.
            with _lock:
                _flusher_started = False
                if not _buffer:
                    return
                _flusher_started = True
            idle = 0


def _ensure_flusher() -> None:
    global _flusher_started, _atexit_registered
    with _lock:
        if _flusher_started:
            return
        _flusher_started = True
        register = not _atexit_registered
        _atexit_registered = True
    threading.Thread(
        target=_flush_loop, name="raytpu-span-flusher", daemon=True
    ).start()
    if register:
        atexit.register(flush)


def _record(span: Span) -> None:
    if _export_dir() is None:
        return
    _buffer.append(span)  # deque append: atomic, no lock
    if not _flusher_started:
        _ensure_flusher()
    if len(_buffer) >= _BUFFER_SPANS:
        flush()  # memory backstop; the age tick normally drains first


def _parent_ctx(
    parent: tuple[str, str] | dict | None
) -> tuple[str, str] | None:
    if isinstance(parent, dict):
        return (parent["trace_id"], parent["span_id"])
    if parent is not None:
        return parent
    return _current.get()


@contextlib.contextmanager
def span(
    name: str,
    parent: tuple[str, str] | dict | None = None,
    *,
    lifecycle: bool = False,
    **attributes: Any,
) -> Iterator[Span | None]:
    """Open a span. ``parent`` may be an injected dict from a TaskSpec, an
    explicit (trace_id, span_id) tuple, or None (inherit the contextvar /
    start a new trace). If the body raises, the span still sets ``end_ns``
    and flushes, with ``status: "error"`` + the exception type recorded.
    ``lifecycle=True``: a once-a-run span, recorded with tracing off too."""
    if not (lifecycle or enabled()):
        yield None
        return
    parent_ctx = _parent_ctx(parent)
    trace_id = parent_ctx[0] if parent_ctx else _new_trace_id()
    record = Span(
        name=name,
        trace_id=trace_id,
        span_id=_new_span_id(),
        parent_id=parent_ctx[1] if parent_ctx else None,
        start_ns=time.time_ns(),
        attributes=attributes,
    )
    token = _current.set((trace_id, record.span_id))
    try:
        yield record
    except BaseException as exc:
        record.set_error(exc)
        raise
    finally:
        _current.reset(token)
        record.end_ns = time.time_ns()
        _record(record)


def emit(
    name: str,
    parent: tuple[str, str] | dict | None = None,
    *,
    start_ns: int,
    end_ns: int | None = None,
    status: str = "ok",
    lifecycle: bool = False,
    **attributes: Any,
) -> Span | None:
    """Record a pre-timed span (for phases whose start predates the call
    site: controller lease parking, in-actor queue wait). Returns the
    recorded Span so callers can chain children off its span_id.
    ``lifecycle`` as for :func:`span`."""
    if not (lifecycle or enabled()):
        return None
    parent_ctx = _parent_ctx(parent)
    record = Span(
        name=name,
        trace_id=parent_ctx[0] if parent_ctx else _new_trace_id(),
        span_id=_new_span_id(),
        parent_id=parent_ctx[1] if parent_ctx else None,
        start_ns=start_ns,
        end_ns=end_ns if end_ns is not None else time.time_ns(),
        status=status,
        attributes=attributes,
    )
    _record(record)
    return record


def begin(
    name: str,
    parent: tuple[str, str] | dict | None = None,
    **attributes: Any,
) -> Span:
    """Hot-path span start: no contextmanager, no contextvar write.

    For per-task call sites (driver submit, worker execute) where the
    `span()` generator + contextvar round-trip is measurable at task
    rates. The caller embeds ``{"trace_id": s.trace_id, "span_id":
    s.span_id}`` wherever the context must ride and MUST call
    ``finish(s)`` on every path. Child spans name the parent explicitly,
    so skipping the contextvar loses nothing. The contextvar is still
    READ for parentage (a task submitted inside a traced actor method
    must chain), just never written. (Parent resolution is inlined:
    this path runs per task and every call costs ~3-8x its raw time in
    GIL handoffs with the io loop thread.) Not gated: per-task callers
    check ``enabled()`` themselves, a lifecycle caller does not."""
    if type(parent) is dict:
        parent_ctx = (parent["trace_id"], parent["span_id"])
    elif parent is not None:
        parent_ctx = parent
    else:
        parent_ctx = _current.get()
    state = _id_prefixes()
    n = next(_id_counter)  # one draw serves both ids of a root span
    return Span(
        name=name,
        trace_id=(
            parent_ctx[0]
            if parent_ctx
            else f"{state[1]}{n & 0xFFFFFFFFFFFFFFFF:016x}"
        ),
        span_id=f"{state[2]}{n & 0xFFFFFFFF:08x}",
        parent_id=parent_ctx[1] if parent_ctx else None,
        start_ns=time.time_ns(),
        attributes=attributes,
    )


def finish(record: Span) -> None:
    """Close + record a span started with begin()."""
    record.end_ns = time.time_ns()
    _record(record)


def set_current(record: Span):
    """Make a begin()-span the ambient parent (returns a reset token).

    For hot-path spans that wrap USER code (worker execute): nested
    submits must chain off them, so the contextvar write span() does is
    needed — but the contextlib generator machinery is not."""
    return _current.set((record.trace_id, record.span_id))


def reset_current(token) -> None:
    _current.reset(token)


def inject(lifecycle: bool = False) -> dict | None:
    """Current span context as a TaskSpec-embeddable dict. ``lifecycle``:
    also with tracing off, for a lifecycle span's child in another
    process (untraced TaskSpecs keep carrying no trace keys)."""
    if not (lifecycle or enabled()):
        return None
    ctx = _current.get()
    if ctx is None:
        return None
    return {"trace_id": ctx[0], "span_id": ctx[1]}


def context_of(record: Span | None) -> dict | None:
    """A specific span's context as an injectable dict (for hand-built
    parent/child links that bypass the contextvar)."""
    if record is None:
        return None
    return {"trace_id": record.trace_id, "span_id": record.span_id}


# -- compact wire context (ISSUE 19) ----------------------------------------
# The rtdag channel plane moves payloads with no RPC frame to ride, so the
# trace context crosses processes as a fixed 25-byte binary segment:
# 16-byte trace_id + 8-byte span_id + 1 flags byte (bit 0 = sampled).
# Hex round-trips exactly (ids are generated as 32/16 hex chars above).

CTX_WIRE_SIZE = 25
_FLAG_SAMPLED = 0x01


def pack_ctx(ctx: dict | tuple | None) -> bytes:
    """Encode an injected context for a channel frame header. Returns
    b"" for None (the disabled path writes zero extra bytes beyond the
    1-byte length that frames always carry)."""
    if ctx is None:
        return b""
    if isinstance(ctx, dict):
        trace_id, span_id = ctx["trace_id"], ctx["span_id"]
    else:
        trace_id, span_id = ctx
    try:
        return (
            bytes.fromhex(trace_id)
            + bytes.fromhex(span_id)
            + bytes([_FLAG_SAMPLED])
        )
    except ValueError:
        # Foreign-format ids (an OTLP bridge injecting its own): drop
        # rather than corrupt the frame.
        return b""


def unpack_ctx(buf) -> dict | None:
    """Decode a pack_ctx segment back to an injectable dict (None for
    empty/short segments)."""
    if not buf or len(buf) < CTX_WIRE_SIZE:
        return None
    b = bytes(buf[:CTX_WIRE_SIZE])
    return {
        "trace_id": b[:16].hex(),
        "span_id": b[16:24].hex(),
        "sampled": bool(b[24] & _FLAG_SAMPLED),
    }


def read_spans(session_dir: str) -> list[dict]:
    """All spans exported under a session (tests + dashboard route)."""
    flush()  # surface this process's buffered spans first
    out: list[dict] = []
    for path in sorted(
        glob.glob(os.path.join(session_dir, "tracing", "spans-*.jsonl"))
    ):
        try:
            with open(path) as fh:
                # A record ends with its newline: a tail without one is
                # another process's append still on its way.
                for line in fh.read().split("\n")[:-1]:
                    if line.strip():
                        out.append(json.loads(line))
        except OSError:
            continue
    return out
