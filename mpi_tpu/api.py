"""Public MPI-like API facade and pluggable backend SPI.

tpu-native rebuild of the reference's L2 layer (/root/reference/mpi.go):

  * package-level operations delegating to one registered backend —
    ``init``/``finalize``/``rank``/``size``/``send``/``receive``
    (mpi.go:93-159);
  * a backend SPI (``Interface``, mpi.go:163-170) with a process-global
    registry (``register``, mpi.go:61-67 — second registration is an error);
  * the ``Raw`` passthrough payload type (mpi.go:75-91, re-exported from
    :mod:`mpi_tpu.utils.serialize`);
  * the duplicate-tag misuse error (``TagError``; the reference declares
    ``TagExists`` at mpi.go:174-182 but never constructs it — its runtime
    panics instead, network.go:469,481,493. Here the declared error type is
    actually raised.)

Semantics preserved from the reference's package doc (mpi.go:20-48):
all calls **block**; ``send`` does not return until the destination has
accepted the message (rendezvous); concurrent sends must use distinct
``{dest, tag}`` pairs and concurrent receives distinct ``{source, tag}``
pairs (mpi.go:122-125, 153-156) — pairs may be reused once the earlier call
returns. Callers use threads for asynchrony, as the reference uses
goroutines.

**New capability beyond the reference** (the north star): collectives.
``reduce``/``bcast``/``allgather``/``allreduce``/``barrier``/``scatter``/
``gather``/``alltoall``/``scan``/``exscan`` — the reference stubs
``AllReduce`` out entirely
(mpi.go:130, 69-71). Backends may implement them natively (the XLA driver
lowers them to ``jax.lax`` collectives over ICI); otherwise the facade falls
back to generic tree/ring algorithms built on ``send``/``receive``
(:mod:`mpi_tpu.collectives_generic`), so every backend gets the full API.
"""

from __future__ import annotations

import os
import threading
import time
from typing import (TYPE_CHECKING, Any, Callable, List, Optional, Protocol,
                    Tuple, runtime_checkable)

if TYPE_CHECKING:
    from .collectives_generic import OpLike

from .utils.serialize import Raw

__all__ = [
    "Interface",
    "register",
    "registered",
    "init",
    "finalize",
    "rank",
    "size",
    "send",
    "receive",
    "sendrecv",
    "iprobe",
    "probe",
    "Request",
    "PersistentRequest",
    "isend",
    "irecv",
    "send_init",
    "recv_init",
    "waitall",
    "waitany",
    "reduce",
    "allreduce",
    "reduce_scatter",
    "bcast",
    "allgather",
    "gather",
    "scatter",
    "alltoall",
    "scan",
    "exscan",
    "barrier",
    "iallreduce",
    "ireduce",
    "ibcast",
    "igather",
    "iallgather",
    "iscatter",
    "ialltoall",
    "ireduce_scatter",
    "ibarrier",
    "Raw",
    "MpiError",
    "TagError",
    "NotInitializedError",
    "set_errhandler",
    "get_errhandler",
    "allreduce_init",
    "bcast_init",
    "barrier_init",
    "pack",
    "unpack",
    "wtime",
    "wtick",
    "receive_any",
    "abort",
]


class MpiError(RuntimeError):
    """Base class for all framework errors.

    Carries the mpi4py ``MPI.Exception`` error-class protocol: code
    written against ``exc.Get_error_class() == MPI.ERR_RANK`` works
    unchanged (classes derive from the exception's type and message —
    :mod:`mpi_tpu.errclass`)."""

    def Get_error_class(self) -> int:
        from . import errclass

        return errclass.classify(self)

    def Get_error_code(self) -> int:
        # No implementation-specific codes beyond the classes here.
        return self.Get_error_class()

    def Get_error_string(self) -> str:
        from . import errclass

        return errclass.error_string(self.Get_error_class())


class TagError(MpiError):
    """A live ``{peer, tag}`` pair was used by a second concurrent call.

    Realizes the reference's declared-but-dead ``TagExists`` error
    (mpi.go:174-182); the reference's runtime instead panics inside
    ``tagManager`` (network.go:469)."""

    def __init__(self, tag: int, peer: int, direction: str = "send"):
        self.tag = tag
        self.peer = peer
        self.direction = direction
        super().__init__(
            f"mpi_tpu: tag {tag} already live for concurrent {direction} "
            f"with peer {peer}; {{peer, tag}} pairs must be unique among "
            f"in-flight operations"
        )


class NotInitializedError(MpiError):
    """An operation was called before ``init()`` / after ``finalize()``."""


@runtime_checkable
class Interface(Protocol):
    """Backend SPI — the rebuild of ``mpi.Interface`` (mpi.go:163-170).

    The six required operations match the reference one-for-one. The
    collective methods are optional: the facade probes for them and falls
    back to the generic send/receive implementations when absent.
    """

    def init(self) -> None: ...
    def finalize(self) -> None: ...
    def rank(self) -> int: ...
    def size(self) -> int: ...
    def send(self, data: Any, dest: int, tag: int) -> None: ...
    def receive(self, source: int, tag: int, out: Optional[Any] = None) -> Any: ...


_lock = threading.Lock()
_backend: Optional[Interface] = None
_registered_explicitly = False
# Reference-counted: under thread-per-rank backends (xla driver) every rank
# thread calls init()/finalize() once, and one rank finishing early must
# not tear the facade down under its siblings. Single-process drivers see
# the same 0→1→0 behavior as the reference's boolean.
_init_count = 0


def _default_backend() -> Interface:
    # The reference wires &Network{} as the default at package init
    # (mpi.go:56). Importing the TCP driver lazily keeps `import mpi_tpu`
    # free of socket/jax side effects.
    from .backends.tcp import TcpNetwork

    return TcpNetwork()


def register(impl: Interface) -> None:
    """Swap in a backend. Mirrors ``mpi.Register`` (mpi.go:61-67): may be
    called at most once, and only before ``init``."""
    global _backend, _registered_explicitly
    with _lock:
        if _registered_explicitly:
            raise MpiError("mpi_tpu: register called twice (mpi.go:63-65 contract)")
        if _init_count > 0:
            raise MpiError("mpi_tpu: register called after init")
        _backend = impl
        _registered_explicitly = True


def registered() -> Interface:
    """Return the active backend, creating the default on first use."""
    global _backend
    with _lock:
        if _backend is None:
            _backend = _default_backend()
        return _backend


def _release_backend(impl: Interface) -> None:
    """Deregister ``impl`` if it is the active backend — used by re-runnable
    hosts (``run_spmd``) so a second run in the same process can register
    again. Not part of the reference surface (Register there is once per
    process-lifetime, mpi.go:61-67); internal on purpose."""
    global _backend, _registered_explicitly, _init_count
    with _lock:
        if _backend is impl:
            _backend = None
            _registered_explicitly = False
            _init_count = 0


def _reset_for_testing() -> None:
    """Clear global registry state (no reference analogue; test hook)."""
    global _backend, _registered_explicitly, _init_count
    with _lock:
        _backend = None
        _registered_explicitly = False
        _init_count = 0


def _require_init() -> Interface:
    if _init_count <= 0:
        raise NotInitializedError("mpi_tpu: call init() first (mpi.go:26-30)")
    return registered()


def init() -> None:
    """Initialize the communication network (mpi.go:96-98). Blocks until
    every rank has connected (network.go:53-65)."""
    global _init_count
    impl = registered()
    impl.init()
    with _lock:
        _init_count += 1
    # Observability bring-up (rank binding for the flight recorder,
    # SIGUSR1 top handler, implicit span enable when a trace sink is
    # configured) — defensive: it must never take init down.
    try:
        from . import observe

        observe.on_init(impl)
    except Exception:  # noqa: BLE001 - observability is best-effort
        pass


def finalize() -> None:
    """Tear down the network (mpi.go:102-104).

    Delegates on *every* call: backends whose ranks are threads (xla,
    hybrid) refcount internally so one rank finishing early cannot tear
    the transport down under its siblings; the facade's own refcount only
    gates ``_require_init``."""
    global _init_count
    impl = registered()
    # Drain and drop this thread's nonblocking-collective chain: a
    # retained tail request would pin its result, and a stale entry
    # could chain a future run (id() reuse) onto this one's corpse.
    chains = getattr(_icoll_tls, "chains", None)
    if chains:
        for key in [k for k in chains if k[0] == id(impl)]:
            _drain_chain(key)
            chains.pop(key, None)
    # Job-wide observability flush BEFORE transport teardown: trace
    # collection is a gather over the live transport (collective when
    # --mpi-trace-out is set on every rank), metrics/summary are local.
    if _init_count > 0:
        try:
            from . import observe

            observe.on_finalize(impl)
        except Exception:  # noqa: BLE001 - observability is best-effort
            pass
    with _lock:
        _init_count = max(0, _init_count - 1)
    impl.finalize()


def rank() -> int:
    """This process's rank in [0, size) (mpi.go:112-114)."""
    return _require_init().rank()


def size() -> int:
    """Total number of ranks (mpi.go:117-119)."""
    return _require_init().size()


# --------------------------------------------------------------------------
# Error handlers (MPI_Errhandler analogue)
# --------------------------------------------------------------------------
#
# The reference documents both styles — "errors may be returned or the
# implementation may panic" (mpi.go:20-21) — which is exactly MPI's
# MPI_ERRORS_RETURN vs MPI_ERRORS_ARE_FATAL choice. The facade defaults
# to returning (raising MpiError); "fatal" aborts the process like
# MPI_ERRORS_ARE_FATAL (and like the reference's panics); a callable is
# an observer hook (logging/cleanup) invoked before the error re-raises.
# The handler fires wherever a facade op EXECUTES — including the
# worker threads of nonblocking/persistent ops, whose bodies are the
# guarded blocking calls. "fatal" therefore aborts the process even
# for an isend misuse (matching MPI_ERRORS_ARE_FATAL's abort-the-job
# semantics); callable handlers must be thread-safe. With "return"
# (default), a worker-thread error is stored and re-raised at wait().

_errhandler: Any = "return"


def set_errhandler(handler: Any) -> Any:
    """Install the world error handler; returns the previous one.

    ``"return"`` (default) raises :class:`MpiError` to the caller;
    ``"fatal"`` prints the error and terminates the process with exit
    code 13 (MPI_ERRORS_ARE_FATAL — matching the reference's panic
    stance, mpi.go:20-21); a callable ``handler(exc)`` is called first,
    then the error raises normally (unless the handler itself raises
    something else)."""
    global _errhandler
    if handler not in ("return", "fatal") and not callable(handler):
        raise MpiError(
            f"mpi_tpu: errhandler must be 'return', 'fatal', or a "
            f"callable, got {handler!r}")
    previous, _errhandler = _errhandler, handler
    return previous


def get_errhandler() -> Any:
    return _errhandler


def _dispatch_error(exc: MpiError) -> None:
    """Route ``exc`` through the installed handler; never returns
    normally (raises or exits)."""
    # Flight recorder: the FIRST fatal typed failure (remote abort,
    # deadline, peer death, wire corruption) dumps this rank's
    # postmortem before the error propagates (docs/OBSERVABILITY.md).
    try:
        from . import observe

        observe.fatal_error_hook(exc)
    except Exception:  # noqa: BLE001 - never mask the real error
        pass
    handler = _errhandler
    if handler == "fatal":
        import sys as _sys
        import traceback as _tb

        _tb.print_exception(type(exc), exc, exc.__traceback__,
                            file=_sys.stderr)
        print("mpi_tpu: aborting (errhandler=fatal)", file=_sys.stderr)
        # MPI_ERRORS_ARE_FATAL aborts the JOB: propagate before exiting
        # so peers raise instead of hanging until their deadlines.
        try:
            notify = getattr(registered(), "notify_abort", None)
            if notify is not None:
                notify(13)
        except BaseException:  # noqa: BLE001 - exiting anyway
            pass
        os._exit(13)
    if callable(handler):
        handler(exc)
    raise exc


def _guarded(fn: Callable) -> Callable:
    """Wrap a facade op so MpiErrors route through the errhandler."""
    import functools

    @functools.wraps(fn)
    def wrapped(*args: Any, **kwargs: Any):
        try:
            return fn(*args, **kwargs)
        except MpiError as exc:
            _dispatch_error(exc)
    return wrapped


def wtime() -> float:
    """Elapsed wall-clock seconds from an arbitrary fixed origin
    (MPI_Wtime; no reference analogue — bounce times with Go's
    ``time.Now``, bounce.go:90-101). Monotonic and per-process: like
    MPI with MPI_WTIME_IS_GLOBAL false, origins differ across ranks,
    so difference timestamps taken on ONE rank."""
    return time.perf_counter()


def wtick() -> float:
    """Resolution of :func:`wtime` in seconds (MPI_Wtick)."""
    info = time.get_clock_info("perf_counter")
    return float(info.resolution)


def _payload_bytes(data: Any) -> int:
    """Best-effort payload size for comm accounting (tracing only)."""
    if isinstance(data, (bytes, bytearray, memoryview)):
        return len(data)
    nbytes = getattr(data, "nbytes", None)
    return int(nbytes) if isinstance(nbytes, int) else 0


@_guarded
def send(data: Any, dest: int, tag: int) -> None:
    """Blocking rendezvous send (mpi.go:126-128): returns only once rank
    ``dest`` has accepted the message (network.go:569,617-624)."""
    impl = _require_init()
    _check_peer(dest, impl)
    _check_tag(tag)
    from .observe import flight
    from .utils import trace

    nbytes = _payload_bytes(data)
    # The span is entered always: it is also a host event of whatever
    # jax.profiler trace is open (utils/trace.py), and nearly free when
    # nothing listens. Counters and the recorder stay behind their flags.
    tok = flight.begin("send", dest, tag, nbytes) if flight.enabled \
        else None
    try:
        if trace.enabled():
            trace.count("comm.send.calls")
            trace.count("comm.send.bytes", nbytes)
        with trace.span("mpi.send", dest=dest, tag=tag, bytes=nbytes):
            impl.send(data, dest, tag)
    except BaseException as exc:
        if tok is not None:
            flight.end(tok, f"error:{type(exc).__name__}")
        raise
    if tok is not None:
        flight.end(tok)


@_guarded
def receive(source: int, tag: int, out: Optional[Any] = None) -> Any:
    """Blocking receive (mpi.go:157-159). Returns the decoded payload.

    ``out`` optionally supplies a preallocated buffer/ndarray to decode
    into, mirroring the reference's receive-into-pointer + ``Raw`` buffer
    reuse semantics (mpi.go:84-90)."""
    impl = _require_init()
    _check_peer(source, impl)
    _check_tag(tag)
    from .observe import flight
    from .utils import trace

    tok = flight.begin("receive", source, tag) if flight.enabled else None
    try:
        with trace.span("mpi.receive", source=source, tag=tag):
            result = impl.receive(source, tag, out=out)
        if trace.enabled():
            trace.count("comm.receive.calls")
            trace.count("comm.receive.bytes", _payload_bytes(result))
    except BaseException as exc:
        if tok is not None:
            flight.end(tok, f"error:{type(exc).__name__}")
        raise
    if tok is not None:
        flight.end(tok)
    return result


def _poll_until(predicate: Callable[[], bool], timeout: Optional[float],
                what: str) -> None:
    """Shared poll-until-deadline loop for blocking probes: raises
    ``MpiError`` naming ``what`` when ``timeout`` elapses. The predicate
    should be pre-validated (it runs every ~0.5 ms)."""
    import time as _time

    deadline = None if timeout is None else _time.monotonic() + timeout
    while not predicate():
        if deadline is not None and _time.monotonic() >= deadline:
            raise MpiError(
                f"mpi_tpu: {what} timed out after {timeout}s")
        _time.sleep(0.0005)


def _iprobe_fn(impl: Interface) -> Callable[[int, int], bool]:
    probe_fn = getattr(impl, "iprobe", None)
    if probe_fn is None:
        raise MpiError(
            f"mpi_tpu: backend {type(impl).__name__} does not support "
            f"iprobe")
    return probe_fn


@_guarded
def iprobe(source: int, tag: int) -> bool:
    """Non-consuming message probe (MPI_Iprobe): True when a message
    from ``source`` with ``tag`` is available — a matching ``receive``
    would complete without blocking on the sender. Never consumes the
    message and never blocks; raises the link failure if the peer's
    connection is poisoned. (No reference analogue; the rendezvous
    drivers report a parked/arrived sender.)"""
    impl = _require_init()
    _check_peer(source, impl)
    _check_tag(tag)
    return bool(_iprobe_fn(impl)(source, tag))


@_guarded
def probe(source: int, tag: int, timeout: Optional[float] = None) -> None:
    """Blocking probe (MPI_Probe): return once a message from ``source``
    with ``tag`` is available (without consuming it); ``MpiError`` on
    timeout."""
    impl = _require_init()
    _check_peer(source, impl)
    _check_tag(tag)
    probe_fn = _iprobe_fn(impl)
    _poll_until(lambda: bool(probe_fn(source, tag)), timeout,
                f"probe(source={source}, tag={tag})")


def exchange(impl: Interface, data: Any, dest: int, source: int, tag: int,
             out: Optional[Any] = None,
             recv_tag: Optional[int] = None) -> Any:
    """Concurrent send+receive against ``impl`` — the shared engine for
    :func:`sendrecv` and the generic collectives' pairwise rounds.
    Deadlock-free where a sequential send-then-receive would
    rendezvous-deadlock. ``recv_tag`` defaults to ``tag``."""
    rtag = tag if recv_tag is None else recv_tag
    result: List[Any] = [None]
    err: List[Optional[BaseException]] = [None]

    def _recv() -> None:
        try:
            result[0] = impl.receive(source, rtag, out=out)
        except BaseException as exc:  # noqa: BLE001 - propagated below
            err[0] = exc

    t = threading.Thread(target=_recv, name="mpi-sendrecv", daemon=True)
    t.start()
    try:
        impl.send(data, dest, tag)
    except BaseException:
        # Don't orphan the posted receive: it would hold its {source, tag}
        # claim forever and could consume-and-ack a message meant for a
        # later call. Backends may support cancellation; fall back to a
        # bounded join otherwise.
        cancel = getattr(impl, "cancel_receive", None)
        if cancel is not None:
            cancel(source, rtag)
        t.join(timeout=30.0)
        raise
    t.join()
    if err[0] is not None:
        raise err[0]
    return result[0]


def _claim_probed(recv: Callable[[int, int], Any],
                  cancel: Optional[Callable[[int, int], bool]],
                  src: int, tag: int) -> Tuple[bool, Any]:
    """ONE bounded claim attempt on a just-probed ``(src, tag)`` — the
    subtle heart of every probe-then-claim loop (receive_any, mprobe,
    improbe), defined once. A probe hit is only a HINT: a sibling may
    consume the message between probe and claim, so the claim is a
    short bounded receive; if nothing lands, the parked receive is
    cancelled (the driver's generation-tagged cancel — the machinery
    ``exchange`` uses). Returns ``(True, payload)`` on a successful
    claim, ``(False, None)`` when a sibling holds the pair (TagError)
    or consumed the message (cancelled); re-raises the receive's own
    errors."""
    req = Request(lambda: recv(src, tag))
    try:
        return True, req.wait(timeout=0.05)
    except TagError:
        return False, None  # a sibling holds this {src, tag} right now
    except MpiError:
        if req.test():
            raise  # the operation's own error — surface it
        # Bounded wait expired: probably consumed by someone else.
        # Cancel our parked receive; if cancellation lost the race (a
        # sender engaged after all), the receive is completing — take it.
        if cancel is not None and cancel(src, tag):
            return False, None
        return True, req.wait(None)


def _receive_any_loop(probe: Callable[[int, int], bool],
                      recv: Callable[[int, int], Any],
                      cancel: Optional[Callable[[int, int], bool]],
                      me: int, n: int, tag: int,
                      timeout: Optional[float],
                      what: str) -> Tuple[int, Any]:
    """Shared ANY_SOURCE engine for the facade and :class:`Comm`:
    poll every source's probe, :func:`_claim_probed` on a hit."""
    deadline = None if timeout is None else time.monotonic() + timeout
    # Rotate the probe order by own rank so N concurrent wildcard
    # receivers don't all stampede the same source first (starting at
    # self is arbitrary).
    order = [(me + i) % n for i in range(n)]
    # A peer that already finalized (its connections closed) makes its
    # probe RAISE — but a wildcard receive awaiting a LIVE sender must
    # not die because an unrelated peer exited first (a legal MPI
    # program: finalize when none of YOUR communication is pending).
    # Transport-death probe errors count as nothing-to-probe; the
    # blacklist clears periodically so a TRANSIENT error cannot turn
    # into permanent deafness. When every remote peer is dead the
    # death is surfaced (self never raises, and a self-only wildcard
    # wait after every peer died is not a supported pattern — use the
    # matched receive(me, tag) for that).
    dead: dict = {}
    sweeps = 0
    while True:
        for src in order:
            if src in dead:
                continue
            try:
                hit = probe(src, tag)
            except (ConnectionError, OSError, MpiError) as exc:
                dead[src] = exc
                continue
            if not hit:
                continue
            won, payload = _claim_probed(recv, cancel, src, tag)
            if won:
                return src, payload
        if n > 1 and len(dead) >= n - 1:
            err = next(iter(dead.values()))
            raise MpiError(
                f"mpi_tpu: {what}(tag={tag}): every remote source is "
                f"unreachable (peers closed); first error: "
                f"{err}") from err
        if deadline is not None and time.monotonic() >= deadline:
            raise MpiError(
                f"mpi_tpu: {what}(tag={tag}) timed out after "
                f"{timeout}s with no matching message")
        sweeps += 1
        if sweeps % 512 == 0:
            dead.clear()  # re-probe: transient errors must recover
        time.sleep(0.0005)


@_guarded
def receive_any(tag: int, timeout: Optional[float] = None
                ) -> Tuple[int, Any]:
    """Receive a message with ``tag`` from WHICHEVER rank sends first —
    MPI_Recv with MPI_ANY_SOURCE, returning ``(source, payload)`` (the
    status' MPI_SOURCE). Works on every driver: available sources are
    discovered via the driver's non-consuming probe, then the winning
    message is claimed with a cancellable bounded receive (see
    :func:`_receive_any_loop` for the race story).

    Concurrency: multiple threads may call ``receive_any`` with the
    same tag — a message taken by a sibling is re-polled past.
    ``timeout=None`` blocks forever; on expiry :class:`MpiError`
    raises with no message consumed. There is no ANY_TAG: tags are
    unbounded 64-bit values here, so a wildcard over them cannot be
    probed."""
    impl = _require_init()
    _check_tag(tag)
    cancel = getattr(impl, "cancel_receive", None)
    return _receive_any_loop(_iprobe_fn(impl), impl.receive, cancel,
                             impl.rank(), impl.size(), tag, timeout,
                             "receive_any")


def abort(code: int = 1) -> None:
    """Terminate this rank immediately (MPI_Abort analogue).

    Best effort: the transport is torn down first so peer ranks fail
    fast — their pending/future operations on this rank poison with a
    connection error instead of hanging until a timeout — then the
    process exits with ``code`` (no atexit handlers; the job is being
    killed). MPI_Abort's whole-job kill reduces to this under the
    fail-fast doctrine the reference documents (mpi.go:10-14): every
    surviving rank errors on its next interaction with the dead one."""
    import sys as _sys

    print(f"mpi_tpu: abort({code})", file=_sys.stderr)
    try:
        from .observe import flight as _flight

        _flight.dump(f"abort({code})")
    except BaseException:  # noqa: BLE001 - exiting anyway
        pass
    try:
        impl = registered()
        # Failure propagation (docs/FAULT_TOLERANCE.md): drivers with an
        # ABORT control frame tell every peer first, so remote ranks
        # raise a typed RemoteAbortError on their pending/future ops
        # instead of discovering the death via connection errors or
        # deadlines.
        notify = getattr(impl, "notify_abort", None)
        if notify is not None:
            notify(code)
        impl.finalize()
    except BaseException:  # noqa: BLE001 - exiting anyway
        pass
    os._exit(code)


@_guarded
def sendrecv(data: Any, dest: int, source: int, tag: int,
             out: Optional[Any] = None) -> Any:
    """Concurrent send+receive, the idiom every reference example spells
    with goroutines (helloworld.go:53-81, bounce.go:86-137). Provided as a
    convenience so Python callers don't need a thread for the common
    exchange pattern."""
    impl = _require_init()
    _check_peer(dest, impl)
    _check_peer(source, impl)
    _check_tag(tag)
    from .observe import flight
    from .utils import trace

    tracing = trace.enabled()
    tok = flight.begin("sendrecv", dest, tag, _payload_bytes(data)) \
        if flight.enabled else None
    try:
        if tracing:
            # Count the exchange's two legs at this level — the internal
            # engine (`exchange`) is also used by collectives_generic,
            # whose traffic is accounted under its own collective name
            # instead.
            trace.count("comm.send.calls")
            trace.count("comm.send.bytes", _payload_bytes(data))
            trace.count("comm.receive.calls")
        with trace.span("mpi.sendrecv", dest=dest, source=source, tag=tag):
            result = exchange(impl, data, dest, source, tag, out=out)
        if tracing:
            trace.count("comm.receive.bytes", _payload_bytes(result))
    except BaseException as exc:
        if tok is not None:
            flight.end(tok, f"error:{type(exc).__name__}")
        raise
    if tok is not None:
        flight.end(tok)
    return result


def _check_peer(peer: int, impl: Interface) -> None:
    n = impl.size()
    if not 0 <= peer < n:
        raise MpiError(f"mpi_tpu: peer rank {peer} out of range [0, {n})")


def _check_tag(tag: int) -> None:
    """World traffic owns the non-negative tag space; the negative half
    is reserved for sub-communicator context regions
    (:mod:`mpi_tpu.comm`), so a negative world tag could capture — or be
    captured by — another communicator's traffic."""
    if tag < 0:
        raise MpiError(
            f"mpi_tpu: tag {tag} is negative; the negative tag space is "
            f"reserved for sub-communicator contexts (mpi_tpu.comm)")


# ---------------------------------------------------------------------------
# Collectives — new capability (reference stub: mpi.go:130, 69-71).
# Native backend methods win; otherwise generic algorithms over send/receive.
# ---------------------------------------------------------------------------

@_guarded
def _collective(name: str, *args: Any, **kwargs: Any) -> Any:
    impl = _require_init()
    # A blocking collective must not race this thread's outstanding
    # nonblocking ones into the positional rendezvous (see
    # _drain_chain); it joins the chain by draining it first.
    _drain_chain((id(impl), 0))
    native = getattr(impl, name, None)
    if native is not None:
        call = lambda: native(*args, **kwargs)  # noqa: E731
    else:
        from . import collectives_generic as gen

        generic = getattr(gen, name)
        call = lambda: generic(impl, *args, **kwargs)  # noqa: E731
    from .observe import flight
    from .utils import trace

    tracing = trace.enabled()
    if tracing or flight.enabled:
        # Straggler substrate: every rank stamps its local arrival at
        # this collective; the in-process drivers report exact skew, and
        # the finalize-time merge computes cross-process skew from the
        # clock-aligned stamps (mpi_tpu.observe.collect).
        from .observe import metrics as _metrics

        _metrics.note_collective_entry(name)
    tok = flight.begin(name, -1, -1,
                       _payload_bytes(args[0]) if args else 0) \
        if flight.enabled else None
    try:
        if tracing:
            trace.count(f"comm.{name}.calls")
            if args:
                trace.count(f"comm.{name}.bytes", _payload_bytes(args[0]))
        with trace.span(f"mpi.{name}"):
            result = call()
    except BaseException as exc:
        if tok is not None:
            flight.end(tok, f"error:{type(exc).__name__}")
        raise
    if tok is not None:
        flight.end(tok)
    return result


def allreduce(data: Any, op: "OpLike" = "sum") -> Any:
    """Combine ``data`` across all ranks with ``op`` and return the result
    on every rank. ``op``: "sum"/"prod"/"min"/"max", or any associative
    callable ``op(a, b) -> combined`` (the MPI_Op_create analogue —
    combination strictly in rank order, so non-commutative ops are
    well-defined; callables reduce on the host tree since XLA cannot
    compile them). The north-star collective (BASELINE.json)."""
    return _collective("allreduce", data, op=op)


def reduce(data: Any, root: int = 0, op: "OpLike" = "sum") -> Optional[Any]:
    """Combine across ranks; result only on ``root`` (None elsewhere)."""
    return _collective("reduce", data, root=root, op=op)


def reduce_scatter(data: Any, op: "OpLike" = "sum") -> Any:
    """Combine ``data`` across ranks, then return only this rank's block:
    the leading axis splits into ``size`` equal blocks and rank ``i``
    gets reduced block ``i`` — the bandwidth-optimal half of ring
    allreduce, exposed directly (ZeRO-style optimizers shard state this
    way). Requires ``data.shape[0] % size == 0``."""
    return _collective("reduce_scatter", data, op=op)


def bcast(data: Any, root: int = 0) -> Any:
    """Broadcast ``root``'s payload to every rank."""
    return _collective("bcast", data, root=root)


def allgather(data: Any) -> List[Any]:
    """Gather every rank's payload to every rank, ordered by rank."""
    return _collective("allgather", data)


def gather(data: Any, root: int = 0) -> Optional[List[Any]]:
    """Gather payloads to ``root`` (list ordered by rank; None elsewhere)."""
    return _collective("gather", data, root=root)


def scatter(data: Optional[List[Any]], root: int = 0) -> Any:
    """Scatter ``root``'s list of per-rank payloads; returns this rank's."""
    return _collective("scatter", data, root=root)


def alltoall(data: List[Any]) -> List[Any]:
    """Personalized all-to-all: element j of this rank's list goes to rank
    j; returns the list of payloads received, ordered by source rank."""
    return _collective("alltoall", data)


class Request:
    """Handle for a nonblocking operation — the async design the
    reference sketches but never builds (the commented-out Send/Wait
    pair at /root/reference/mpi.go:132-152). ``isend``/``irecv`` start
    the blocking operation on a worker thread (the reference's
    "callers use goroutines" doctrine made first-class) and return one
    of these; ``wait()`` joins it, re-raising any error (including
    ``TagError`` for a duplicate live ``{peer, tag}``) and returning
    the received payload for receives. Once ``wait`` returns, the
    ``{peer, tag}`` pair is free for reuse — exactly the contract the
    sketch specifies."""

    def __init__(self, fn, cancel_hook=None):
        self._result: Any = None
        self._exc: Optional[BaseException] = None
        self._cancel_hook = cancel_hook
        self._cancelled = False

        def run():
            try:
                self._result = fn()
            except BaseException as exc:  # re-raised at wait()
                self._exc = exc

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def test(self) -> bool:
        """True once the operation has completed (without blocking).
        Completion includes failure — ``wait`` reports which."""
        return not self._thread.is_alive()

    def cancel(self) -> bool:
        """MPI_Cancel: best-effort cancellation of a pending operation.

        True when the operation was actually cancelled (a receive whose
        message had not yet been matched); the canonical completion
        sequence is still ``cancel(); wait()`` — after a successful
        cancel, ``wait`` returns ``None`` and :attr:`cancelled` is
        True, rather than raising (MPI's cancelled-request contract).
        A request with nothing cancellable (sends mid-rendezvous, an
        already-matched receive, collectives) returns False and
        completes normally — MPI says cancellation is permitted to
        fail.

        The retract hook only bites once the worker thread has CLAIMED
        the tag — a cancel racing a just-posted irecv would no-op and
        leave ``wait()`` blocked forever — so this retries over a
        short bounded window until the claim exists (normally
        microseconds away) or the operation completes by itself."""
        if self._cancel_hook is None:
            return False
        deadline = time.monotonic() + 1.0
        while not self.test():
            try:
                hit = self._cancel_hook()
            except Exception:
                return False  # invalid envelope etc: wait() reports it
            if hit:
                self._cancelled = True
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.002)
        return False

    @property
    def cancelled(self) -> bool:
        """True when :meth:`cancel` succeeded (MPI_Test_cancelled)."""
        return self._cancelled

    def wait(self, timeout: Optional[float] = None) -> Any:
        """Block until completion; return the received payload (None for
        sends). Raises the operation's error, or ``MpiError`` on
        timeout. A successfully cancelled request completes with
        ``None`` instead of raising (check :attr:`cancelled`)."""
        self._thread.join(timeout)
        if self._thread.is_alive():
            raise MpiError(
                f"mpi_tpu: Request.wait timed out after {timeout}s")
        if self._exc is not None:
            from .backends.rendezvous import ReceiveCancelled

            if self._cancelled and isinstance(self._exc,
                                              ReceiveCancelled):
                return None  # cancelled completion, per MPI semantics
            raise self._exc
        # The payload arrived despite a racing cancel (MPI: a
        # successful cancel means NO part of the message was received
        # — so a delivered message proves the cancel did not happen).
        self._cancelled = False
        return self._result


def isend(data: Any, dest: int, tag: int) -> Request:
    """Nonblocking send: returns immediately with a :class:`Request`;
    ``wait()`` blocks until the receiver accepted the payload (the
    rendezvous ack — the reference sketch's ``Wait``, mpi.go:145-151).

    Routed through the facade's :func:`send` so peer validation and
    trace accounting cover nonblocking traffic too (validation errors
    surface at ``wait()``)."""
    _require_init()
    return Request(lambda: send(data, dest, tag))


def irecv(source: int, tag: int, out: Optional[Any] = None) -> Request:
    """Nonblocking receive: ``wait()`` returns the payload. Supports
    ``Request.cancel()`` when the backend can retract an unmatched
    receive (``cancel_receive`` — the tcp/shm and xla drivers can)."""
    _require_init()
    impl = registered()
    hook = getattr(impl, "cancel_receive", None)
    return Request(lambda: receive(source, tag, out),
                   cancel_hook=(None if hook is None
                                else lambda: hook(source, tag)))


def waitall(requests: List[Optional[Request]],
            timeout: Optional[float] = None) -> List[Any]:
    """Wait on every request; results in order; first error re-raised.
    ``None`` slots (requests already consumed by :func:`waitany` —
    MPI_REQUEST_NULL) are skipped with a ``None`` result. ``timeout`` is
    a TOTAL deadline across the whole set — a hung request makes the
    call raise after ~``timeout`` seconds, not ``len(requests) *
    timeout`` (requests still running at the deadline are reported in
    the error and keep their daemon worker threads)."""
    import time as _time

    deadline = None if timeout is None else _time.monotonic() + timeout
    results: List[Any] = []
    first_exc: Optional[BaseException] = None
    for req in requests:
        if req is None:
            results.append(None)
            continue
        left = None if deadline is None else max(
            0.0, deadline - _time.monotonic())
        try:
            results.append(req.wait(left))
        except BaseException as exc:
            if first_exc is None:
                first_exc = exc
            results.append(None)
    if first_exc is not None:
        pending = [i for i, r in enumerate(requests)
                   if r is not None and not r.test()]
        if pending:
            exc = MpiError(
                f"mpi_tpu: waitall deadline expired with "
                f"{len(pending)}/{len(requests)} requests still running "
                f"(indices {pending})")
            exc.partial_results = results
            raise exc from first_exc
        raise first_exc
    return results


class PersistentRequest:
    """A restartable communication operation (MPI_Send_init /
    MPI_Recv_init): the envelope — peer, tag, and for sends a payload
    *supplier* — is fixed once, then each :meth:`start` launches one
    instance and :meth:`wait` completes it, freeing the ``{peer, tag}``
    pair for the next ``start``. The idiom for fixed communication
    patterns in iterative codes (halo exchanges, pipelined rings), where
    MPI amortizes envelope setup; here it amortizes the closure and
    keeps the call sites declarative."""

    def __init__(self, fn: Callable[[], Any],
                 launcher: Optional[Callable[[Callable[[], Any]],
                                             "Request"]] = None):
        self._fn = fn
        # How start() turns fn into a Request. Persistent COLLECTIVES
        # pass a launcher that chains onto the caller thread's
        # i-collective chain (see _persistent_collective) so their
        # instances keep the collective ordering contract; p2p ops use
        # a plain Request.
        self._launch = launcher if launcher is not None else Request
        self._active: Optional[Request] = None

    def start(self) -> "PersistentRequest":
        """Launch one instance. Every started instance must be completed
        with :meth:`wait` before the next ``start`` (the MPI contract) —
        otherwise a quickly-failed instance's stored error (or a
        receive's payload) would be silently discarded here."""
        if self._active is not None:
            if not self._active.test():
                raise MpiError(
                    "mpi_tpu: PersistentRequest.start() while the "
                    "previous instance is still in flight; wait() first")
            raise MpiError(
                "mpi_tpu: PersistentRequest.start() before wait() on the "
                "completed previous instance (its result/error would be "
                "lost)")
        self._active = self._launch(self._fn)
        return self

    def test(self) -> bool:
        return self._active is not None and self._active.test()

    def wait(self, timeout: Optional[float] = None) -> Any:
        """Complete the in-flight instance (payload for receives).

        A timeout leaves the instance active so ``wait`` can be retried
        — discarding it would orphan a live ``{peer, tag}`` operation
        and lose its eventual result. Operation errors consume the
        instance (it completed; ``start`` may be called again)."""
        if self._active is None:
            raise MpiError(
                "mpi_tpu: PersistentRequest.wait() before start()")
        active = self._active
        try:
            result = active.wait(timeout)
        except MpiError:
            if not active.test():
                raise  # genuine timeout: instance retained for retry
            # Completed during the timeout window, or the operation's
            # own MpiError: consume the instance and surface its outcome.
            self._active = None
            return active.wait(0)
        except BaseException:
            # Consume only if the instance actually completed; an
            # interrupted join (KeyboardInterrupt/SystemExit) leaves the
            # operation live — keep it so a later wait() can finish it
            # instead of orphaning a live {peer, tag}.
            if active.test():
                self._active = None
            raise
        self._active = None
        return result


def send_init(data_or_supplier: Any, dest: int, tag: int) -> PersistentRequest:
    """Persistent send (MPI_Send_init). ``data_or_supplier`` may be the
    payload itself (same bytes every start) or a zero-arg callable
    evaluated at each :meth:`~PersistentRequest.start` — the analogue of
    MPI's buffer re-read, for payloads that change between iterations."""
    _require_init()
    supplier = _as_supplier(data_or_supplier)
    return PersistentRequest(lambda: send(supplier(), dest, tag))


def recv_init(source: int, tag: int,
              out: Optional[Any] = None) -> PersistentRequest:
    """Persistent receive (MPI_Recv_init); each completed ``wait()``
    returns that instance's payload."""
    _require_init()
    return PersistentRequest(lambda: receive(source, tag, out))


def _as_supplier(data_or_supplier: Any) -> Callable[[], Any]:
    """The callable-vs-payload coercion every ``*_init`` shares: a
    zero-arg callable is re-read at each start (MPI's buffer re-read);
    anything else is the fixed payload."""
    if callable(data_or_supplier):
        return data_or_supplier
    return lambda: data_or_supplier


def _persistent_collective(name: str, supplier: Callable[[], Tuple],
                           ) -> PersistentRequest:
    impl = _require_init()
    # start() must join the caller thread's i-collective chain — a
    # plain Request would run _collective in a fresh worker thread
    # whose empty TLS makes its _drain_chain a no-op, letting the
    # instance race outstanding nonblocking collectives (or another
    # in-flight persistent instance) into the positional rendezvous.
    return PersistentRequest(
        lambda: _collective(name, *supplier()),
        launcher=lambda fn: _chained_request((id(impl), 0), fn))


def allreduce_init(data_or_supplier: Any,
                   op: "OpLike" = "sum") -> PersistentRequest:
    """Persistent allreduce (MPI-4 MPI_Allreduce_init). Each
    :meth:`~PersistentRequest.start` runs one allreduce round; as with
    every collective, all ranks must start their instances in the same
    collective order. ``data_or_supplier`` may be a zero-arg callable
    re-read at each start (the MPI buffer-re-read analogue)."""
    supplier = _as_supplier(data_or_supplier)
    return _persistent_collective("allreduce", lambda: (supplier(), op))


def bcast_init(data_or_supplier: Any = None,
               root: int = 0) -> PersistentRequest:
    """Persistent broadcast (MPI_Bcast_init); each completed ``wait()``
    returns that round's payload."""
    supplier = _as_supplier(data_or_supplier)
    return _persistent_collective("bcast", lambda: (supplier(), root))


def barrier_init() -> PersistentRequest:
    """Persistent barrier (MPI_Barrier_init)."""
    return _persistent_collective("barrier", lambda: ())


# --------------------------------------------------------------------------
# Pack / Unpack (MPI_Pack / MPI_Unpack analogue)
# --------------------------------------------------------------------------

def pack(*items: Any) -> bytes:
    """Serialize ``items`` into one contiguous buffer (MPI_Pack).

    Each item is encoded with the wire codec (the same typed encoding
    ``send`` uses — ndarrays round-trip dtype/shape losslessly) behind
    a u64 length prefix, so a packed buffer is self-describing and can
    ride any transport or file as a single payload. The reference's
    gob encoding plays this role implicitly; here it is explicit."""
    import struct as _struct

    from .utils.serialize import encode as _encode

    parts: List[bytes] = []
    for item in items:
        payload = _encode(item)
        parts.append(_struct.pack("<Q", len(payload)))
        parts.append(payload)
    return b"".join(parts)


def unpack(buf: Any) -> Tuple[Any, ...]:
    """Inverse of :func:`pack`: decode every packed item, in order."""
    import struct as _struct

    from .utils.serialize import decode as _decode

    # Normalize to a byte-granular view: a caller-supplied memoryview
    # with itemsize > 1 (e.g. over a uint64 array) would make len()
    # count elements while unpack_from offsets count bytes.
    if isinstance(buf, memoryview):
        view = buf.cast("B") if buf.contiguous else memoryview(bytes(buf))
    elif isinstance(buf, (bytes, bytearray)):
        view = memoryview(buf)
    else:
        view = memoryview(bytes(buf))
    out: List[Any] = []
    pos = 0
    total = len(view)
    while pos < total:
        if pos + 8 > total:
            raise MpiError(
                f"mpi_tpu: truncated pack buffer at offset {pos}")
        (n,) = _struct.unpack_from("<Q", view, pos)
        pos += 8
        if pos + n > total:
            raise MpiError(
                f"mpi_tpu: pack item of {n} bytes overruns buffer "
                f"({total - pos} left)")
        out.append(_decode(bytearray(view[pos:pos + n])))
        pos += n
    return tuple(out)


def waitany(requests: List[Optional[Request]],
            timeout: Optional[float] = None) -> Tuple[int, Any]:
    """Block until ANY request completes; return ``(index, result)`` and
    leave the rest running (MPI_Waitany). The completed slot is set to
    ``None`` in the caller's list — MPI's MPI_REQUEST_NULL convention —
    so the standard drain loop (`for _ in range(n): waitany(reqs)`)
    visits every request exactly once; ``None`` slots are skipped.
    Raises the completed operation's error; ``MpiError`` if every slot
    is already ``None`` or the deadline passes with nothing done."""
    import time as _time

    live = [i for i, r in enumerate(requests) if r is not None]
    if not live:
        raise MpiError(
            "mpi_tpu: waitany with no live requests (empty list or all "
            "slots already consumed)")
    deadline = None if timeout is None else _time.monotonic() + timeout
    while True:
        for i in live:
            req = requests[i]
            if req.test():
                requests[i] = None  # consumed: MPI_REQUEST_NULL
                return i, req.wait(0)
        if deadline is not None and _time.monotonic() >= deadline:
            raise MpiError(
                f"mpi_tpu: waitany timed out after {timeout}s with "
                f"{len(live)} requests still running")
        _time.sleep(0.0005)


# ---------------------------------------------------------------------------
# Nonblocking collectives (MPI-3 MPI_Iallreduce family): the blocking
# collective launched on a worker thread, completion via Request — the
# same doctrine as isend/irecv ("callers use goroutines", made
# first-class). The MPI ordering rule carries over: every rank must
# START its nonblocking collectives in the same order — and because the
# drivers match collectives positionally (shared barrier sessions /
# sequential tag blocks), consecutive nonblocking collectives on the
# same communicator are internally CHAINED in launch order: each
# executes only after the previous one launched by this thread
# completed. Progress therefore overlaps with the caller's compute
# (the point of I-collectives), not with each other — racing worker
# threads into the rendezvous would otherwise pair rank A's allreduce
# with rank B's bcast.
# ---------------------------------------------------------------------------

_icoll_tls = threading.local()


def _chain_slot(key: Any) -> Optional["Request"]:
    """This thread's outstanding chained request for ``key`` (pruned
    once complete, so finished results don't stay pinned)."""
    chains = getattr(_icoll_tls, "chains", None)
    if chains is None:
        chains = _icoll_tls.chains = {}
    prev = chains.get(key)
    if prev is not None and prev.test():
        del chains[key]
        prev = None
    return prev


def _drain_chain(key: Any) -> None:
    """Complete any outstanding chained i-collective for ``key`` before
    a BLOCKING collective on the same communicator proceeds — otherwise
    the blocking call would race the chained worker into the positional
    rendezvous and mismatch collective kinds across ranks. Errors stay
    with their own request."""
    prev = _chain_slot(key)
    if prev is not None:
        try:
            prev.wait()
        except BaseException:
            # prev's own stored error belongs to prev's owner — swallow.
            # But an interrupt of the join (KeyboardInterrupt/SystemExit
            # with prev still live) must propagate: proceeding would race
            # the still-running worker into the rendezvous.
            if not prev.test():
                raise
        _chain_slot(key)  # prune the completed entry


def _chained_request(key: Any, fn: Callable[[], Any]) -> "Request":
    """Launch ``fn`` on a worker thread AFTER the previous chained
    request for ``key`` (per launching thread) completes; errors stay
    with their own request (the successor still runs — matching MPI,
    where a failed collective does not cancel queued ones)."""
    prev = _chain_slot(key)

    def run() -> Any:
        if prev is not None:
            try:
                prev.wait()
            except BaseException:  # noqa: BLE001 — surfaced on prev
                pass
        return fn()

    req = Request(run)
    _icoll_tls.chains[key] = req
    return req


def _icollective(name: str) -> Callable[..., "Request"]:
    def launch(*args: Any, **kwargs: Any) -> Request:
        impl = _require_init()
        blocking = globals()[name]
        return _chained_request((id(impl), 0),
                                lambda: blocking(*args, **kwargs))

    launch.__name__ = f"i{name}"
    launch.__qualname__ = f"i{name}"
    launch.__doc__ = (
        f"Nonblocking {name} (MPI_I{name}): starts the "
        f"collective and returns a :class:`Request`; ``wait()`` yields "
        f"what blocking :func:`{name}` returns. All ranks must start "
        f"their nonblocking collectives in the same order; consecutive "
        f"ones chain in launch order (overlap is with caller compute).")
    return launch


iallreduce = _icollective("allreduce")
ireduce = _icollective("reduce")
ibcast = _icollective("bcast")
igather = _icollective("gather")
iallgather = _icollective("allgather")
iscatter = _icollective("scatter")
ialltoall = _icollective("alltoall")
ireduce_scatter = _icollective("reduce_scatter")
ibarrier = _icollective("barrier")


def scan(data: Any, op: "OpLike" = "sum") -> Any:
    """Inclusive prefix reduction in rank order: rank r gets the
    combination of ranks 0..r (MPI_Scan)."""
    return _collective("scan", data, op=op)


def exscan(data: Any, op: "OpLike" = "sum") -> Optional[Any]:
    """Exclusive prefix reduction: rank r gets ranks 0..r-1 combined;
    rank 0 gets None (MPI_Exscan)."""
    return _collective("exscan", data, op=op)


def barrier() -> None:
    """Block until every rank has entered the barrier."""
    return _collective("barrier")
