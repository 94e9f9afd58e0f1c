"""XLA driver — ranks on a device mesh, collectives over ICI.

The tpu-native realization of the reference's process model (SURVEY.md §7,
BASELINE.json north_star). Where the reference maps rank → OS process and
moves bytes over TCP (network.go), this driver maps **rank → device** on a
:class:`jax.sharding.Mesh` axis inside one process:

  * ``init``/``finalize`` — mesh construction + a rank barrier, replacing
    the O(N²) socket handshake (network.go:122-351): XLA already knows the
    slice topology, so bootstrap is local;
  * ``send``/``receive`` — blocking tagged rendezvous between rank threads
    (exactly the reference's contract, mpi.go:122-159) with device-to-device
    array movement (``jax.device_put`` → ICI transfer on TPU slices);
  * collectives — the north star: array payloads are assembled into one
    global sharded array and reduced by a **single compiled XLA collective**
    over the mesh (``mpi_tpu.parallel.collectives``), which rides ICI.
    ``deterministic=True`` uses the canonical binomial tree for
    bitwise-identical results to the TCP driver. Object payloads
    (strings, dicts, ...) use in-process handoff. The payload's type
    picks the way, and nothing else does: a ``jax.Array`` with
    ``ndim >= 1`` is its rank's shard of the global array as it is
    (never read to the host), and ``allreduce`` / ``reduce`` /
    ``reduce_scatter`` / ``scan`` / ``exscan`` hand the rank its result
    as a ``jax.Array`` committed to its own device, as soon as the
    program is dispatched; numpy, lists and scalars (0-d arrays too) are
    placed on the devices, and the result is read back into numpy.
    Ranks of one call may differ: each gets the type it gave.
    ``bcast`` / ``gather`` / ``allgather`` take a device payload in the
    same way but return numpy (their result is replicated);
    ``alltoall`` / ``scatter`` stack their lists on the host.

Programming model. The reference is SPMD-by-processes: one binary, N
processes, behavior branches on ``Rank()`` (mpi.go:8-14). Here the same
user code runs SPMD-by-threads: :func:`run_spmd` launches one thread per
rank, each bound to its device, so reference-style programs (helloworld,
bounce) run unmodified on a v4-8 — while ``jit``-heavy code is free to use
the functional layer directly for zero-overhead collectives inside a
single trace.

Single-process scope: this driver covers every rank the process can
address (a full v4-8). Multi-host DCN spans are the hybrid driver's job
(hierarchical: XLA within a host, TCP across hosts — see
``mpi_tpu.backends.hybrid``).
"""

from __future__ import annotations

import copy
import threading
import time
from collections import OrderedDict
from typing import TYPE_CHECKING, Any, Callable, Dict, List, Optional, Sequence, Tuple

if TYPE_CHECKING:
    from ..collectives_generic import OpLike

import numpy as np

from ..api import MpiError, _payload_bytes
from ..utils import trace
from .rendezvous import ReceiveCancelled, Rendezvous

__all__ = ["XlaNetwork", "run_spmd"]


def _jax():
    import jax

    return jax


def _nbytes(payloads: Sequence[Any]) -> int:
    """Bytes of the array payloads among ``payloads`` (span attribute)."""
    return sum(_payload_bytes(p) for p in payloads)


# --------------------------------------------------------------------------
# Rank-binding inheritance.
#
# The reference's rank is per-*process*, so any goroutine may call
# Send/Receive (helloworld.go:53-81 does exactly that). Here a rank is a
# per-*thread* binding, so threads the user spawns (and the facade's own
# sendrecv helper) would come up unbound. While any run_spmd is active,
# Thread.start is wrapped so a thread started by a bound thread inherits
# its binding — reference-style threaded programs run unmodified.
# --------------------------------------------------------------------------

_patch_lock = threading.Lock()
_active_networks: List["XlaNetwork"] = []
_orig_thread_start = threading.Thread.start


def _patched_start(self: threading.Thread) -> None:
    # Runs in the *parent* thread: snapshot its bindings for the child.
    bindings = [(net, net._tls.rank) for net in list(_active_networks)
                if getattr(net._tls, "rank", None) is not None]
    if bindings and not getattr(self, "_mpi_rank_bound", False):
        orig_run = self.run

        def run_bound() -> None:
            for net, r in bindings:
                net._tls.rank = r
            orig_run()

        self.run = run_bound
        self._mpi_rank_bound = True
    _orig_thread_start(self)


def _activate_inheritance(net: "XlaNetwork") -> None:
    with _patch_lock:
        _active_networks.append(net)
        if threading.Thread.start is _orig_thread_start:
            threading.Thread.start = _patched_start


def _deactivate_inheritance(net: "XlaNetwork") -> None:
    with _patch_lock:
        if net in _active_networks:
            _active_networks.remove(net)
        if not _active_networks:
            threading.Thread.start = _orig_thread_start


class _CollectiveAborted(MpiError):
    """A rank's collective ended because ``_CollectiveSession.abort`` was
    called: collateral of whichever rank failed first."""

    def __init__(self) -> None:
        super().__init__("mpi_tpu: collective aborted (another rank failed)")


class _CollectiveSession:
    """Rank-thread synchronization for native collectives.

    One rendezvous a collective. Every rank stores its payload and
    counts itself in; the rank that completes the count leads at once, on
    the thread it is on (it is awake and holds the GIL, so nobody is woken
    for the combined computation to begin), publishes the results and
    opens the other ranks' gates. Every other rank sleeps once, on its own
    gate (a lock acquired in C, the GIL dropped), from its arrival until
    the results are there, and wakes once.

    Reusable across sequential collectives with no second rendezvous: a
    rank enters collective g+1 only after it has read its result of g,
    and g+1 has a leader only when all n have entered, so nothing of g
    is overwritten under a reader and no gate is opened twice.
    Collectives must be invoked in the same order by all ranks — the
    standard MPI requirement the generic layer documents too."""

    def __init__(self, n: int):
        self._n = n
        self._lock = threading.Lock()
        self._count = 0
        self._aborted = False
        # One gate a rank, held shut between collectives. ``_asleep[r]``
        # says rank r is behind its gate: whoever clears it (the leader
        # or ``abort``, under ``_lock``) opens the gate, so once only.
        self._gates = [threading.Lock() for _ in range(n)]
        for gate in self._gates:
            gate.acquire()
        self._asleep = [False] * n
        self._slots: List[Any] = [None] * n
        self._results: List[Any] = [None] * n
        self._error: Optional[BaseException] = None
        # Per-collective arrival stamps (perf ns): all rank threads
        # share one clock, so the leader reads EXACT skew —
        # the straggler-detection source for the in-process drivers.
        self._arrivals: List[int] = [0] * n
        # The collective the leader is running: the ``op=`` of the stage
        # spans its helpers record (one leader at a time).
        self.op = "collective"

    def _note_skew(self, name: str) -> None:
        from ..observe import flight, metrics

        if not (flight.enabled or trace.enabled()):
            return
        arr = self._arrivals
        lo, hi = min(arr), max(arr)
        if lo <= 0:
            return
        metrics.note_session_skew(name, (hi - lo) / 1e3, arr.index(hi))

    def _release(self, abort: bool = False) -> bool:
        """Open the gate of every sleeping rank; returns whether the
        session is aborted."""
        with self._lock:
            self._aborted = self._aborted or abort
            for r, asleep in enumerate(self._asleep):
                if asleep:
                    self._asleep[r] = False
                    self._gates[r].release()
            return self._aborted

    def abort(self) -> None:
        """Fail every rank asleep in a collective, and every rank that
        enters one from now on, with the ``collective aborted`` error."""
        self._release(abort=True)

    def run(self, rank: int, value: Any,
            leader: Callable[[List[Any]], List[Any]],
            name: str = "collective",
            path: Optional[Callable[[List[Any]], str]] = None) -> Any:
        """``path(slots)``, where given, names the way the payloads take
        into the program (``device`` / ``host`` / ``mixed``): an
        attribute of the leader's span."""
        self._slots[rank] = value
        self._arrivals[rank] = time.perf_counter_ns()
        with self._lock:
            if self._aborted:
                raise _CollectiveAborted()
            self._count += 1
            leads = self._count == self._n
            if leads:
                self._count = 0
            else:
                self._asleep[rank] = True
        if leads:
            slots = list(self._slots)
            self._note_skew(name)
            self.op = name
            try:
                attrs = {} if path is None else {"path": path(slots)}
                with trace.span("xla.coll.leader", op=name, **attrs):
                    self._results = leader(slots)
                self._error = None
            except BaseException as exc:  # noqa: BLE001 - re-raised on all ranks
                self._error = exc
            aborted = self._release()
        else:
            if trace.enabled():
                trace.count("xla.coll.sleeps")
            with trace.span("xla.coll.release_wait", op=name):
                self._gates[rank].acquire()
            aborted = self._aborted
        if aborted:
            raise _CollectiveAborted()
        error, result = self._error, self._results[rank]
        if error is not None:
            raise MpiError(
                f"mpi_tpu: collective failed on leader: {error}") from error
        return result


class _MeshCollectives:
    """Compiled-collective engine over an ordered device list.

    All of the xla driver's native collectives live here so one
    machinery serves both the world (one engine per driver) and any
    communicator group (one engine per ``(context, members)``, built by
    :meth:`XlaNetwork.group_collectives`): a leader thread runs ONE
    compiled XLA program over the engine's (sub-)mesh — psum/all_gather/
    ppermute over ICI on TPU — with host-tree fallbacks when ranks share
    devices (oversubscription) and object-payload fallbacks preserving
    the generic driver's semantics. ``rank_of`` maps the calling thread
    to its rank WITHIN this engine (world rank for the world engine,
    group rank for a communicator's)."""

    def __init__(self, net: "XlaNetwork", devices: List[Any], mesh,
                 rank_of: Callable[[], int]):
        self._net = net
        self._devices = list(devices)
        self._n = len(self._devices)
        self._mesh = mesh
        self._rank_of = rank_of
        self._coll = _CollectiveSession(self._n)
        self._jit_cache: Dict[Tuple, Any] = {}
        trace.listen_compiles()     # a collective of a new shape compiles
        self._fillers: "OrderedDict[Tuple, Any]" = OrderedDict()

    def _myrank(self) -> int:
        return self._rank_of()

    @property
    def deterministic_collectives(self) -> bool:
        return self._net.deterministic_collectives

    def _check_rank(self, r: int) -> None:
        if not 0 <= r < self._n:
            raise MpiError(
                f"mpi_tpu: rank {r} out of range [0, {self._n})")


    @staticmethod
    def _validate_payloads(slots: List[Any]) -> None:
        """Cross-rank shape/dtype agreement + the float64-downcast guard.
        Enforced identically on the mesh and oversubscribed paths so a
        program's behavior never depends on the rank/device ratio. Reads
        ``.shape`` / ``.dtype`` only: a device payload stays where it is."""
        jax = _jax()
        shape, dtype = slots[0].shape, slots[0].dtype
        for i, s in enumerate(slots):
            if s.shape != shape or s.dtype != dtype:
                raise MpiError(
                    f"mpi_tpu: collective payload mismatch: rank 0 has "
                    f"{shape}/{dtype}, rank {i} has {s.shape}/{s.dtype}")
        if dtype.itemsize == 8 and dtype.kind in "fiu" \
                and not jax.config.jax_enable_x64:
            raise MpiError(
                f"mpi_tpu: {dtype} collective payload would silently "
                f"downcast — enable 64-bit mode (JAX_ENABLE_X64=1 or "
                f"jax.config.update('jax_enable_x64', True)) or send "
                f"32-bit data")

    # How payloads reach a compiled collective, and the stages of one as
    # spans under the session's ``xla.coll.leader``
    # (docs/OBSERVABILITY.md). The payload's type decides: a *device
    # payload* (a ``jax.Array`` with ``ndim >= 1``) becomes its rank's
    # shard of the mesh-global input as it is — moved device to device
    # first if it is not committed to the rank's device — and where the
    # program's output is sharded over the ranks too (allreduce, reduce,
    # reduce_scatter, scan, exscan) that rank gets its result as the
    # ``jax.Array`` on its own device; every other payload is read into
    # numpy, placed, and its rank's result read back into numpy. The
    # global array's leading axis is the payload's: shape
    # ``(n * shape[0], *shape[1:])`` over ``P("rank")``, so the payloads
    # are the input's shards and the output's shards are the results, with
    # no program to add or drop an axis. Stages: host_read (payloads ->
    # numpy), device_put (the assembly of the global array, with whatever
    # placement it needs), launch (dispatch; returns before the device
    # ends), read_back (wait for the device, results device -> host).
    # host_read and read_back are entered only where a payload or a
    # result takes that way, so a call of device payloads has neither.

    def _stage(self, stage: str, nbytes: int):
        return trace.span("xla.coll." + stage, op=self._coll.op,
                          bytes=nbytes)

    @staticmethod
    def _on_device(payload: Any) -> bool:
        """Whether ``payload`` is a device payload (see above)."""
        return isinstance(payload, _jax().Array) and payload.ndim >= 1

    def _path(self, slots: List[Any], host_tree: bool = False) -> str:
        """The leader span's ``path=``: ``device`` when every payload of
        the call stays on the device, ``host`` when none does."""
        staying = 0 if host_tree else sum(map(self._on_device, slots))
        return "device" if staying == len(slots) \
            else "mixed" if staying else "host"

    def _arrays(self, slots: List[Any], on_host: bool = False) -> List[Any]:
        """Every payload as an array: device payloads as they are (unless
        ``on_host``), the others read into numpy."""
        stays = [not on_host and self._on_device(s) for s in slots]
        if trace.enabled():
            trace.count("xla.coll.device_payloads", sum(stays))
            trace.count("xla.coll.host_payloads", len(stays) - sum(stays))
        if all(stays):
            return list(slots)
        with self._stage("host_read", _nbytes(
                [s for s, stay in zip(slots, stays) if not stay])):
            return [s if stay else np.asarray(s)
                    for s, stay in zip(slots, stays)]

    def _global_array(self, arrays: List[Any]):
        """One mesh-sharded global array whose shard on device i is
        ``arrays[i]`` — the input format XLA collectives want. A device
        payload already committed to its rank's device is taken as it
        is; ``None`` entries (bcast's non-roots) become cached zero
        blocks that are never read."""
        jax = _jax()
        from jax.sharding import NamedSharding, PartitionSpec as P

        first = next(a for a in arrays if a is not None)
        shape, dtype = first.shape, first.dtype
        with self._stage("device_put", _nbytes(arrays)):
            shards = [
                self._filler_shard(d, shape, dtype) if a is None
                else a if isinstance(a, jax.Array) and a.committed
                and a.devices() == {d}
                else jax.device_put(a, d)
                for a, d in zip(arrays, self._devices)
            ]
            return jax.make_array_from_single_device_arrays(
                (self._n * shape[0], *shape[1:]),
                NamedSharding(self._mesh, P("rank")), shards)

    def _launch(self, garr, kind: str, op: str = "",
                deterministic: bool = False, root: int = 0):
        """Dispatch the compiled ``kind`` program on the global array."""
        fn = self._collective_fn(kind, op, deterministic, root)
        with self._stage("launch", garr.nbytes):
            return fn(garr)

    def _read_back(self, out) -> np.ndarray:
        """A replicated result on the host (waits for the device)."""
        with self._stage("read_back", out.nbytes):
            return np.asarray(out)

    def _per_rank(self, global_arr, like: Sequence[Any] = ()) -> List[Any]:
        """A mesh-sharded result as per-rank results: rank i's shard as
        the ``jax.Array`` on its device where ``like[i]`` is one (its
        payload stayed on the device; nobody waits for the program),
        read back into numpy otherwise."""
        jax = _jax()
        by_device = {s.device: s.data for s in global_arr.addressable_shards}
        per = [by_device[d] for d in self._devices]
        stays = [isinstance(a, jax.Array) for a in like] or [False] * self._n
        to_host = [i for i, stay in enumerate(stays) if not stay]
        if to_host:
            with self._stage("read_back",
                             sum(per[i].nbytes for i in to_host)):
                for i in to_host:
                    per[i] = np.asarray(per[i])
        return per

    def _collective_fn(self, kind: str, op: str = "",
                       deterministic: bool = False, root: int = 0):
        key = (kind, op, deterministic, root) if kind == "bcast" \
            else (kind, op, deterministic)
        fn = self._jit_cache.get(key)
        if fn is not None:
            return fn
        jax = _jax()
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..parallel import collectives as C

        # Every per_shard sees its rank's payload as it is: ``shape``, no
        # leading axis of 1 (the helpers' comment block above).
        if kind == "allreduce":
            def per_shard(x):
                return C.allreduce(x, "rank", op=op,
                                   deterministic=deterministic)

            out_specs = P("rank")
        elif kind == "allgather":
            def per_shard(x):
                # The full (n, *shape) stack, replicated on every device.
                return C.allgather(x, "rank", axis=0)

            out_specs = P()
        elif kind == "alltoall":
            def per_shard(x):
                # x: (n, *shape) — row j is my payload for rank j; after
                # the exchange, row j holds rank j's payload to me.
                return C.alltoall(x, "rank", split_axis=0, concat_axis=0)

            out_specs = P("rank")
        elif kind == "bcast":
            def per_shard(x):
                # Real data only on root's shard (fillers elsewhere); the
                # all_gather + static index is XLA's broadcast idiom over
                # ICI.
                return C.bcast(x, root, "rank")

            out_specs = P()
        elif kind == "prefix":
            # Rank-order prefix reduction (scan/exscan). The
            # ``deterministic`` slot carries ``exclusive`` for this kind
            # (the order is always the fixed left fold).
            def per_shard(x):
                return C.prefix_reduce(x, "rank", op=op,
                                       exclusive=deterministic)

            out_specs = P("rank")
        elif kind == "reduce_scatter":
            def per_shard(x):
                # x: (L, *shape); each rank keeps its reduced L/n block.
                # deterministic → canonical size-selected order; the
                # ring/tree choice lives in parallel.collectives next
                # to allreduce's so the rule can never fork.
                return C.reduce_scatter(x, "rank", op=op,
                                        deterministic=deterministic)

            out_specs = P("rank")
        else:  # pragma: no cover - future kinds
            raise MpiError(f"unknown collective kind {kind}")

        fn = jax.jit(jax.shard_map(per_shard, mesh=self._mesh,
                                   in_specs=P("rank"), out_specs=out_specs,
                                   check_vma=False))
        self._jit_cache[key] = fn
        return fn

    _FILLER_CACHE = 32

    def _filler_shard(self, device, shape, dtype):
        """A cached zeros block on ``device`` — the placeholder shard for
        global arrays whose real data lives on one device (bcast input);
        its contents are never read. LRU-capped like DevicePipe's."""
        key = (device, shape, str(dtype))
        arr = self._fillers.get(key)
        if arr is not None:
            self._fillers.move_to_end(key)
            return arr
        arr = _jax().device_put(np.zeros(shape, dtype), device)
        self._fillers[key] = arr
        while len(self._fillers) > self._FILLER_CACHE:
            self._fillers.popitem(last=False)
        return arr

    def _rides_compiled(self, payload) -> bool:
        """Whether ``payload`` can ride a compiled path: array-typed,
        ndim >= 1, and a dtype XLA will not rewrite (int64/float64
        without x64 fall back to the object path, which returns payloads
        untouched). Looks at the payload's type, ``ndim`` and ``dtype``
        only."""
        jax = _jax()
        if self._mesh is None or not isinstance(
                payload, (np.ndarray, jax.Array)) or payload.ndim < 1:
            return False
        try:
            return jax.dtypes.canonicalize_dtype(payload.dtype) \
                == payload.dtype
        except TypeError:
            return False

    def _uniform(self, slots: List[Any]) -> bool:
        """Whether all payloads can ride a compiled path and have one
        shape/dtype."""
        first = slots[0]
        return all(self._rides_compiled(s) and s.shape == first.shape
                   and s.dtype == first.dtype for s in slots)

    def _uniform_arrays(self, slots: List[Any],
                        on_host: bool = False) -> Optional[List[Any]]:
        """The payloads as arrays (:meth:`_arrays`) if uniform, else
        None."""
        return self._arrays(slots, on_host) if self._uniform(slots) \
            else None

    def allreduce(self, data: Any, op: "OpLike" = "sum",
                  deterministic: Optional[bool] = None) -> Any:
        """North-star collective: one XLA reduction over the mesh.

        Payloads must be numeric (anything ``np.asarray`` maps to a
        numeric dtype, matching what the generic driver can reduce);
        a non-numeric payload raises on every rank.

        The result has the type of this rank's payload: a ``jax.Array``
        with ``ndim >= 1`` stays on the device all the way and comes back
        as a ``jax.Array`` committed to this rank's device, same shape
        and dtype, returned as soon as the program is dispatched (jax's
        own idiom: whatever uses it waits for it; the payload is not
        donated). Anything else comes back as numpy, after the device
        has ended. Ranks may differ in one call."""
        det = (self.deterministic_collectives if deterministic is None
               else deterministic)
        me = self._myrank()
        host_tree = self._mesh is None or callable(op)

        def leader(slots: List[Any]) -> List[Any]:
            np_slots = self._arrays(slots, on_host=host_tree)
            if np_slots[0].dtype.kind not in "fiubc":
                raise MpiError(
                    f"mpi_tpu: allreduce requires numeric payloads, got "
                    f"dtype {np_slots[0].dtype}")
            scalar = np_slots[0].ndim == 0
            self._validate_payloads(np_slots)
            if host_tree:
                # Oversubscribed ranks share devices → no mesh; user
                # callable ops (MPI_Op_create analogue) are host
                # functions XLA cannot compile. Either way reduce on
                # the host in the canonical order — ring or tree by the
                # shared size rule (always deterministic, bitwise-equal
                # to the TCP oracle on both sides of the threshold).
                from ..collectives_generic import canonical_combine

                total = canonical_combine(np_slots, op)
                per = [total.copy() for _ in range(self._n)]
            else:
                # 0-d payloads are host payloads: one element a rank.
                garr = self._global_array(
                    [s[None] for s in np_slots] if scalar else np_slots)
                out = self._launch(garr, "allreduce", op, det)
                per = self._per_rank(out, like=np_slots)
            if scalar:
                per = [p.reshape(())[()] for p in per]
            return per

        from ..collectives_generic import check_op

        check_op(op)
        return self._coll.run(
            me, data, leader, name="allreduce",
            path=lambda slots: self._path(slots, host_tree))

    def barrier(self) -> None:
        self._coll.run(self._myrank(), None,
                       lambda slots: [None] * self._n, name="barrier")

    def bcast(self, data: Any, root: int = 0) -> Any:
        """Array payloads broadcast as ONE compiled XLA program: the
        root's array becomes its shard of a mesh-global input (cached
        zero fillers stand in elsewhere — never read), and the compiled
        ``all_gather`` + static index rides ICI. Objects take the
        in-process handoff (deep-copied per rank); broadcast arrays may
        alias across ranks — treat them as read-only, as with
        ``allgather``."""
        self._check_rank(root)

        def leader(slots: List[Any]) -> List[Any]:
            payload = slots[root]
            if not self._rides_compiled(payload):
                return [payload if i == root else copy.deepcopy(payload)
                        for i in range(self._n)]
            arr, = self._arrays([payload])
            garr = self._global_array(
                [arr if i == root else None for i in range(self._n)])
            rows = self._read_back(self._launch(garr, "bcast", root=root))
            return [rows for _ in range(self._n)]

        return self._coll.run(self._myrank(), data, leader, name="bcast")

    def gather(self, data: Any, root: int = 0) -> Optional[List[Any]]:
        """Uniform array payloads ride the compiled all_gather program
        (XLA's ICI-ring collective; the non-root copies are the cost of
        staying on one compiled path) and only root keeps the result;
        otherwise in-process handoff."""
        self._check_rank(root)

        def leader(slots: List[Any]) -> List[Any]:
            np_slots = self._uniform_arrays(slots)
            if np_slots is None:
                return [list(slots) if i == root else None
                        for i in range(self._n)]
            garr = self._global_array(np_slots)
            rows = self._read_back(self._launch(garr, "allgather"))
            gathered = [rows[i] for i in range(self._n)]
            return [gathered if i == root else None
                    for i in range(self._n)]

        return self._coll.run(self._myrank(), data, leader, name="gather")

    def allgather(self, data: Any) -> List[Any]:
        """Array payloads of matching shape/dtype gather with ONE compiled
        XLA all_gather over the mesh (ICI on TPU); anything else (objects,
        ragged shapes) uses the in-process handoff. Returned entries may
        alias between ranks, matching the generic driver's semantics.

        The dtype gate is canonicalization only — anything XLA would
        rewrite (int64/float64/complex128 without x64) takes the
        in-process handoff, which returns payloads untouched; bfloat16
        stays on the compiled path."""

        def leader(slots: List[Any]) -> List[Any]:
            np_slots = self._uniform_arrays(slots)
            if np_slots is None:
                return [list(slots) for _ in range(self._n)]
            garr = self._global_array(np_slots)
            rows = self._read_back(self._launch(garr, "allgather"))
            gathered = [rows[i] for i in range(self._n)]
            # Fresh list per rank (elements may alias; the containers must
            # not — same contract as the fallback path).
            return [list(gathered) for _ in range(self._n)]

        return self._coll.run(self._myrank(), data, leader,
                              name="allgather")

    def scatter(self, data: Optional[List[Any]], root: int = 0) -> Any:
        """A uniform array list scatters by committing the stacked
        payload straight to the ``P('rank')`` sharding: argument
        placement is the one legal entry point for root-local data onto
        the mesh (an XLA program's inputs must already live on the
        mesh's devices), and it moves each shard exactly once to its
        owner. Each rank's result is device-resident on its own device.
        Mixed payloads take the in-process handoff."""
        self._check_rank(root)
        jax = _jax()

        def leader(slots: List[Any]) -> List[Any]:
            items = slots[root]
            if items is None or len(items) != self._n:
                raise MpiError(
                    f"mpi_tpu: scatter root needs a list of exactly "
                    f"{self._n} payloads")
            np_items = self._uniform_arrays(list(items), on_host=True)
            if np_items is None:
                return list(items)
            from jax.sharding import NamedSharding, PartitionSpec as P

            with self._stage("device_put", _nbytes(np_items)):
                out = jax.device_put(np.concatenate(np_items),
                                     NamedSharding(self._mesh, P("rank")))
            return self._per_rank(out)

        return self._coll.run(self._myrank(), data, leader, name="scatter")

    def alltoall(self, data: List[Any]) -> List[Any]:
        """Uniform payload matrices exchange with ONE compiled XLA
        AllToAll over the mesh; mixed payloads use in-process handoff."""
        if len(data) != self._n:
            raise MpiError(
                f"mpi_tpu: alltoall needs exactly {self._n} payloads, "
                f"got {len(data)}")

        def leader(slots: List[List[Any]]) -> List[List[Any]]:
            flat = [p for row in slots for p in row]
            np_flat = self._uniform_arrays(flat, on_host=True)
            if np_flat is None:
                return [[slots[src][dst] for src in range(self._n)]
                        for dst in range(self._n)]
            n = self._n
            stacked = [np.stack(np_flat[i * n:(i + 1) * n])
                       for i in range(n)]  # (n, *shape) per source rank
            garr = self._global_array(stacked)          # (n * n, *shape)
            out = self._launch(garr, "alltoall")
            return [list(row) for row in self._per_rank(out)]

        return self._coll.run(self._myrank(), data, leader,
                              name="alltoall")

    def reduce(self, data: Any, root: int = 0, op: "OpLike" = "sum") -> Optional[Any]:
        self._check_rank(root)
        result = self.allreduce(data, op=op)
        return result if self._myrank() == root else None

    def reduce_scatter(self, data: Any, op: "OpLike" = "sum",
                       deterministic: Optional[bool] = None) -> Any:
        """Reduce across ranks and keep this rank's block of the result:
        the payload's leading axis splits into ``size`` equal blocks and
        rank ``i`` returns reduced block ``i`` — one compiled
        ``psum_scatter`` (or the binomial tree + slice when
        ``deterministic``) over the mesh. The block has the type of this
        rank's payload, as in :meth:`allreduce`."""
        det = (self.deterministic_collectives if deterministic is None
               else deterministic)
        from ..collectives_generic import canonical_combine, check_op

        check_op(op)
        host_tree = self._mesh is None or callable(op)

        def leader(slots: List[Any]) -> List[Any]:
            np_slots = self._arrays(slots, on_host=host_tree)
            self._validate_payloads(np_slots)
            shape = np_slots[0].shape
            if len(shape) < 1 or shape[0] % self._n:
                raise MpiError(
                    f"mpi_tpu: reduce_scatter payload leading axis "
                    f"{shape or 'scalar'} must divide into {self._n} "
                    f"equal blocks")
            m = shape[0] // self._n
            if host_tree:
                total = canonical_combine(np_slots, op)
                return [total[i * m:(i + 1) * m].copy()
                        for i in range(self._n)]
            garr = self._global_array(np_slots)
            out = self._launch(garr, "reduce_scatter", op, det)
            return self._per_rank(out, like=np_slots)

        return self._coll.run(
            self._myrank(), data, leader, name="reduce_scatter",
            path=lambda slots: self._path(slots, host_tree))

    def scan(self, data: Any, op: "OpLike" = "sum") -> Any:
        """Inclusive prefix reduction in rank order, as ONE compiled
        program (``parallel.collectives.prefix_reduce`` — the jittable
        MPI_Scan whose left-fold order is the cross-backend bitwise
        contract); scalars, objects, and callable ops fold on the host
        in the same order. On the compiled path the prefix has the type
        of this rank's payload, as in :meth:`allreduce`."""
        return self._prefix(data, op, exclusive=False)

    def exscan(self, data: Any, op: "OpLike" = "sum") -> Optional[Any]:
        """Exclusive prefix reduction; rank 0 gets None (MPI_Exscan)."""
        return self._prefix(data, op, exclusive=True)

    def _prefix(self, data: Any, op: "OpLike", exclusive: bool) -> Any:
        from ..collectives_generic import check_op, combine

        check_op(op)

        def host_fold(slots: List[Any]) -> bool:
            # The compiled path is float/int/uint only: jnp's
            # add/multiply/minimum/maximum reject bool and complex in
            # ways numpy's don't, and prefix_reduce's exclusive identity
            # doesn't exist for them either — those (plus scalars,
            # objects, callable ops, oversubscription) take the host
            # fold, identical order.
            return callable(op) or not self._uniform(slots) \
                or slots[0].dtype.kind not in "fiu"

        def leader(slots: List[Any]) -> List[Any]:
            if host_fold(slots):
                # Raw slots (combine() normalizes operands), so rank 0's
                # inclusive result stays the caller's own payload type —
                # matching collectives_generic.scan. One running left
                # fold yields every rank's prefix in n-1 combines (the
                # O(n^2) per-rank refold would be paid exactly where
                # combines are most expensive).
                items = list(slots)
                prefixes: List[Any] = []
                acc = items[0]
                for it in items[1:]:
                    prefixes.append(acc)
                    acc = combine(acc, it, op)
                if exclusive:
                    return [None] + prefixes
                return prefixes + [acc]
            np_slots = self._arrays(slots)
            self._validate_payloads(np_slots)
            per = self._per_rank(self._launch(
                self._global_array(np_slots), "prefix", op, exclusive),
                like=np_slots)
            if exclusive:
                per = [None] + list(per[1:])  # rank 0: MPI_Exscan contract
            return per

        return self._coll.run(
            self._myrank(), data, leader,
            name="exscan" if exclusive else "scan",
            path=lambda slots: self._path(slots, host_fold(slots)))


class XlaNetwork:
    """Backend implementing the :class:`mpi_tpu.api.Interface` SPI over a
    device mesh. Construct with the rank count (defaults to every visible
    device) and hand user code to :func:`run_spmd`."""

    # Rank threads share this process's address space, so RMA windows
    # over this driver support MPI_Win_shared_query (mpi_tpu.window).
    SUPPORTS_SHARED_WINDOWS = True
    # ... and one process-global tracer buffer: the observe layer's
    # trace collection writes the shared buffer once (rank threads
    # appear as tid lanes) instead of gathering N duplicate copies.
    SHARED_PROCESS_TRACER = True

    def __init__(self, n: Optional[int] = None,
                 devices: Optional[Sequence[Any]] = None,
                 deterministic_collectives: bool = False,
                 oversubscribe: bool = False):
        jax = _jax()
        from ..parallel.mesh import make_mesh

        if devices is None:
            devices = jax.devices()[: n] if n is not None else jax.devices()
        if n is not None and len(devices) < n:
            if oversubscribe and devices:
                # Reference parity: N ranks on fewer cores is always legal
                # (gompirun spawns N processes regardless of CPU count) —
                # map ranks onto devices round-robin.
                base = list(devices)
                devices = [base[r % len(base)] for r in range(n)]
            else:
                raise MpiError(
                    f"mpi_tpu: need {n} devices for {n} ranks, have "
                    f"{len(devices)} (pass oversubscribe=True to share)")
        self._devices = list(devices)
        self._n = len(self._devices)
        # With oversubscribed (duplicate) devices there is no valid mesh;
        # native collectives then run on the canonical numpy tree instead
        # of a compiled XLA collective.
        if len(set(self._devices)) == len(self._devices):
            self._mesh = make_mesh(devices=self._devices)
        else:
            self._mesh = None
        self._tls = threading.local()
        self._init_barrier = threading.Barrier(self._n)
        # One rendezvous per ordered (src, dst) pair, created lazily.
        self._pairs: Dict[Tuple[int, int], Rendezvous] = {}
        self._pairs_lock = threading.Lock()
        self._pipe = None  # lazy DevicePipe (compiled p2p transfers)
        self._initialized = False
        self.deterministic_collectives = deterministic_collectives
        # Native collectives: one world engine + lazily-built engines per
        # communicator group (group_collectives), all sharing this
        # driver's devices and rank binding.
        self._world_coll = _MeshCollectives(self, self._devices, self._mesh,
                                            self._myrank)
        self._group_colls: "OrderedDict[Tuple, _MeshCollectives]" = \
            OrderedDict()

    # -- rank binding --------------------------------------------------------

    def bind_rank(self, rank: int) -> None:
        """Associate the calling thread with ``rank`` (run_spmd does this)."""
        if not 0 <= rank < self._n:
            raise MpiError(f"mpi_tpu: rank {rank} out of range [0, {self._n})")
        self._tls.rank = rank

    def _myrank(self) -> int:
        r = getattr(self._tls, "rank", None)
        if r is None:
            if self._n == 1:
                return 0
            raise MpiError(
                "mpi_tpu: calling thread has no rank binding — run your "
                "program under mpi_tpu.backends.xla.run_spmd(fn, n)")
        return r

    def device(self, rank: Optional[int] = None):
        """The jax device backing ``rank`` (default: calling thread's)."""
        return self._devices[self._myrank() if rank is None else rank]

    @property
    def mesh(self):
        return self._mesh

    # -- Interface ------------------------------------------------------------

    def init(self) -> None:
        """Barrier across all rank threads (the bootstrap analogue —
        network.go:122-159 collapses to a thread barrier because XLA
        already knows the topology)."""
        self._myrank()  # validates binding
        if self._n > 1:
            try:
                self._init_barrier.wait(timeout=60.0)
            except threading.BrokenBarrierError as exc:
                raise MpiError(
                    "mpi_tpu: init barrier broken (a rank failed to start)"
                ) from exc
        self._initialized = True

    def finalize(self) -> None:
        self._initialized = False

    def rank(self) -> int:
        return self._myrank()

    def size(self) -> int:
        return self._n

    def host_key(self) -> str:
        """All xla-driver ranks share one process (one host) — a single
        key, so ``Comm.split_type("host")`` yields the whole world."""
        return "local"

    # -- point-to-point -------------------------------------------------------

    def _pair(self, src: int, dst: int) -> Rendezvous:
        key = (src, dst)
        with self._pairs_lock:
            rv = self._pairs.get(key)
            if rv is None:
                rv = Rendezvous(send_peer=dst, recv_peer=src)
                self._pairs[key] = rv
            return rv

    def send(self, data: Any, dest: int, tag: int) -> None:
        """Blocking rendezvous send. Array payloads move to the
        destination rank's device through a **compiled ppermute program**
        (:class:`mpi_tpu.parallel.p2p.DevicePipe`) — a pure ICI hop on
        TPU with no host round-trip of the payload, the tpu-native data
        path replacing the reference's socket write (network.go:562-567).
        Host objects are copied, preserving the reference's value
        semantics (gob round-trip implies the receiver never aliases
        sender memory)."""
        me = self._myrank()
        self._check_rank(dest)
        jax = _jax()
        if isinstance(data, jax.Array):
            with trace.span("xla.transfer", dest=dest, tag=tag):
                payload = self._device_transfer(data, dest)
        elif isinstance(data, np.ndarray):
            payload = data.copy()
        elif isinstance(data, (bytes, str, int, float, bool, complex,
                               type(None))):
            payload = data  # immutable
        else:
            payload = copy.deepcopy(data)
        if trace.enabled():
            trace.count(f"wire.xla.tx.bytes.peer{dest}",
                        _payload_bytes(data))
        with trace.span("xla.rendezvous_send", dest=dest, tag=tag):
            self._pair(me, dest).send(tag, payload)

    def _device_transfer(self, data, dest: int):
        """Compiled device→device move of a jax.Array to ``dest``'s device.

        Single-device source arrays ride the DevicePipe's cached ppermute
        executable (ICI); already-in-place, sharded, or uncommitted
        arrays — and oversubscribed/meshless configurations — fall back
        to ``jax.device_put`` (which is a no-op when already resident)."""
        jax = _jax()
        dst_dev = self._devices[dest]
        src_devs = getattr(data, "devices", lambda: set())()
        if (self._mesh is not None and len(src_devs) == 1
                and getattr(data, "committed", True)):
            src_dev = next(iter(src_devs))
            if src_dev != dst_dev:
                with self._pairs_lock:
                    if self._pipe is None:
                        from ..parallel.p2p import DevicePipe

                        self._pipe = DevicePipe()
                    pipe = self._pipe
                return pipe.transfer(data, src_dev, dst_dev)
        return jax.device_put(data, dst_dev)

    def receive(self, source: int, tag: int, out: Optional[Any] = None) -> Any:
        me = self._myrank()
        self._check_rank(source)
        with trace.span("xla.recv_wait", source=source, tag=tag):
            payload = self._pair(source, me).receive(tag)
        if trace.enabled():
            trace.count(f"wire.xla.rx.bytes.peer{source}",
                        _payload_bytes(payload))
        if out is not None and isinstance(out, np.ndarray) \
                and isinstance(payload, np.ndarray) \
                and out.shape == payload.shape and out.dtype == payload.dtype:
            out[...] = payload
            return out
        return payload

    def cancel_receive(self, source: int, tag: int) -> bool:
        me = self._myrank()
        self._check_rank(source)
        exc = ReceiveCancelled(
            f"mpi_tpu: receive(source={source}, tag={tag}) cancelled")
        return self._pair(source, me).cancel(tag, exc)

    def iprobe(self, source: int, tag: int) -> bool:
        """Non-consuming MPI_Iprobe: True when the sender is parked at
        this pair's rendezvous with ``tag`` (a receive would complete
        immediately)."""
        me = self._myrank()
        self._check_rank(source)
        return self._pair(source, me).probe(tag)

    def _check_rank(self, r: int) -> None:
        if not 0 <= r < self._n:
            raise MpiError(f"mpi_tpu: peer rank {r} out of range [0, {self._n})")

    # -- native collectives (world engine; see _MeshCollectives) -------------

    def allreduce(self, data: Any, op: "OpLike" = "sum",
                  deterministic: Optional[bool] = None) -> Any:
        return self._world_coll.allreduce(data, op=op,
                                          deterministic=deterministic)

    def barrier(self) -> None:
        return self._world_coll.barrier()

    def bcast(self, data: Any, root: int = 0) -> Any:
        return self._world_coll.bcast(data, root=root)

    def gather(self, data: Any, root: int = 0) -> Optional[List[Any]]:
        return self._world_coll.gather(data, root=root)

    def allgather(self, data: Any) -> List[Any]:
        return self._world_coll.allgather(data)

    def scatter(self, data: Optional[List[Any]], root: int = 0) -> Any:
        return self._world_coll.scatter(data, root=root)

    def alltoall(self, data: List[Any]) -> List[Any]:
        return self._world_coll.alltoall(data)

    def reduce(self, data: Any, root: int = 0,
               op: "OpLike" = "sum") -> Optional[Any]:
        return self._world_coll.reduce(data, root=root, op=op)

    def reduce_scatter(self, data: Any, op: "OpLike" = "sum",
                       deterministic: Optional[bool] = None) -> Any:
        return self._world_coll.reduce_scatter(data, op=op,
                                               deterministic=deterministic)

    def scan(self, data: Any, op: "OpLike" = "sum") -> Any:
        return self._world_coll.scan(data, op=op)

    def exscan(self, data: Any, op: "OpLike" = "sum") -> Optional[Any]:
        return self._world_coll.exscan(data, op=op)

    # -- communicator group engines ------------------------------------------

    def group_collectives(self, members, ctx: int) -> _MeshCollectives:
        """Compiled-collective engine for a communicator group: the
        members' devices become a sub-mesh and every collective in the
        suite runs as one compiled XLA program over it (host/object
        fallbacks included), exactly like the world path. One shared
        engine per ``(ctx, members)`` — all member rank threads must use
        the same instance, since it holds their rendezvous barrier."""
        key = (int(ctx), tuple(int(m) for m in members))
        with self._pairs_lock:
            eng = self._group_colls.get(key)
            if eng is not None:
                self._group_colls.move_to_end(key)
                return eng
            from ..parallel.mesh import make_mesh

            for m in key[1]:
                self._check_rank(m)
            devs = [self._devices[m] for m in key[1]]
            mesh = (make_mesh(devices=devs)
                    if len(set(devs)) == len(devs) else None)
            members_t = key[1]
            eng = _MeshCollectives(
                self, devs, mesh,
                lambda mt=members_t: mt.index(self._myrank()))
            self._group_colls[key] = eng
            # LRU backstop for leaked communicators (dup-per-call
            # patterns): each engine pins compiled executables and filler
            # device buffers. Comm.free() is the precise release; the cap
            # only evicts least-recently-used engines, which are safe to
            # drop unless more than _GROUP_ENGINE_CACHE communicators are
            # *concurrently* mid-collective (an evicted-but-live group
            # would re-create its engine and split its rendezvous).
            while len(self._group_colls) > self._GROUP_ENGINE_CACHE:
                self._group_colls.popitem(last=False)
        return eng

    _GROUP_ENGINE_CACHE = 128

    def release_group_collectives(self, members, ctx: int) -> None:
        """Drop the group engine for ``(ctx, members)`` (Comm.free):
        frees its compiled programs and filler buffers. Idempotent; must
        not race a collective in flight on that communicator."""
        key = (int(ctx), tuple(int(m) for m in members))
        with self._pairs_lock:
            self._group_colls.pop(key, None)

    def abort_collectives(self) -> None:
        """Abort every collective session (world + group engines) so rank
        threads blocked in a collective fail fast when a sibling dies."""
        with self._pairs_lock:
            engines = [self._world_coll, *self._group_colls.values()]
        for e in engines:
            e._coll.abort()



def drive_rank_threads(fn: Callable[[], Any], *, nranks: int,
                       bind: Callable[[int], None],
                       abort: Callable[[], None],
                       inherit_net: "XlaNetwork",
                       facade_net: Any,
                       name_prefix: str = "mpi-rank",
                       register_facade: bool = True,
                       on_failure: Optional[Callable[[], None]] = None
                       ) -> List[Any]:
    """Shared thread-per-rank driver used by ``run_spmd`` (xla) and
    ``run_spmd_hybrid``: spawn, bind, join with a bounded grace period
    once any rank errors, release the facade, and re-raise the root-cause
    error (broken-barrier collateral is reported only if nothing else
    failed)."""
    from .. import api

    if register_facade:
        api.register(facade_net)
    results: List[Any] = [None] * nranks
    errors: List[Optional[BaseException]] = [None] * nranks
    _activate_inheritance(inherit_net)

    def runner(r: int) -> None:
        bind(r)
        try:
            results[r] = fn()
        except BaseException as exc:  # noqa: BLE001 - aggregated below
            errors[r] = exc
            abort()

    threads = [threading.Thread(target=runner, args=(r,),
                                name=f"{name_prefix}-{r}", daemon=True)
               for r in range(nranks)]
    for t in threads:
        t.start()
    # Join, but once any rank has errored give stragglers a bounded grace
    # period (a failed partner can leave a rank parked in a rendezvous that
    # will never complete — don't hang the launcher on it).
    import time as _time

    try:
        deadline: Optional[float] = None
        while True:
            alive = [t for t in threads if t.is_alive()]
            if not alive:
                break
            if any(e is not None for e in errors):
                if deadline is None:
                    deadline = _time.monotonic() + 10.0
                elif _time.monotonic() > deadline:
                    break
            for t in alive:
                t.join(timeout=0.1)
    finally:
        _deactivate_inheritance(inherit_net)
        if register_facade:
            api._release_backend(facade_net)
        if on_failure is not None and any(e is not None for e in errors):
            on_failure()
    # Prefer the root-cause error: ranks that merely saw a broken barrier
    # (init or collective) are collateral of whichever rank failed first.
    secondary = None
    for e in errors:
        if e is None:
            continue
        if isinstance(e, _CollectiveAborted) or isinstance(e, MpiError) \
                and isinstance(e.__cause__, threading.BrokenBarrierError):
            secondary = secondary or e
            continue
        raise e
    if secondary is not None:
        raise secondary
    return results


def run_spmd(fn: Callable[[], Any], n: Optional[int] = None,
             net: Optional[XlaNetwork] = None,
             register_facade: bool = True) -> List[Any]:
    """Run ``fn`` SPMD: one thread per rank, each bound to a mesh device —
    the in-process analogue of ``gompirun N prog`` (gompirun.go:28-93).

    ``fn`` is reference-style user code: it calls ``mpi_tpu.init()``,
    branches on ``mpi_tpu.rank()``, communicates, ``mpi_tpu.finalize()``.
    Returns the per-rank return values. The first rank exception is
    re-raised after all threads stop."""
    # Explicit rank counts oversubscribe like gompirun does (N processes
    # regardless of core count, gompirun.go:46-51).
    network = net or XlaNetwork(n=n, oversubscribe=True)

    def abort() -> None:
        network._init_barrier.abort()
        network.abort_collectives()

    return drive_rank_threads(
        fn, nranks=network.size(), bind=network.bind_rank, abort=abort,
        inherit_net=network, facade_net=network,
        register_facade=register_facade)
