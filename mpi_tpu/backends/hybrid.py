"""Hybrid driver — XLA ranks within a host, TCP between hosts.

The tpu deployment model the reference cannot express: a TPU pod is
*hosts × local chips*, where one OS process drives several chips. The
reference's answer to multi-node is one TCP process per rank
(network.go:122-159); the tpu-native answer is hierarchical:

  * **intra-host**: ranks are threads over the local device mesh — the
    :class:`mpi_tpu.backends.xla.XlaNetwork` driver verbatim (compiled
    ICI collectives, in-process rendezvous p2p);
  * **inter-host**: one TCP connection mesh between *hosts* (the DCN
    analogue) — the :class:`mpi_tpu.backends.tcp.TcpNetwork` driver
    verbatim, carrying cross-host p2p frames and the host-leader legs of
    hierarchical collectives.

Global rank layout is contiguous per host, host order = TCP rank order
(sorted addresses, network.go:94-109): host ``h`` with ``L_h`` local ranks
owns global ranks ``[offset_h, offset_h + L_h)``. Local counts are
exchanged at init, so heterogeneous hosts work.

Collectives are hierarchical (the BASELINE.json config-5 shape): e.g.
``allreduce`` = XLA allreduce across local ranks → TCP allreduce of the
per-host partials among host leaders (canonical binomial tree,
:mod:`mpi_tpu.collectives_generic`) → XLA bcast back to local ranks. The
slow tier therefore carries one buffer per host, not one per rank.

Cross-host point-to-point composes ``(src, dst, user_tag)`` into a single
host-level wire tag (bit 62 set — disjoint from user tags, which live
below 2^48, and from the collective tag space at 2^48..2^62). Cross-host
sends therefore require ``0 <= tag < 2**32 - 2**21`` (the top 2**21 of
the field is the partitioned-p2p + RMA window-service band: those
reserved i64 tag slices remap into it so passive-target lock/unlock
and MPI-4 partitioned sends work across hosts) and at most 2**15
global ranks; intra-host tags are unrestricted.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import (TYPE_CHECKING, Any, Callable, List, Optional,
                    Sequence, Tuple)

if TYPE_CHECKING:
    from ..collectives_generic import OpLike

from .. import collectives_generic as G
from ..api import MpiError
from ..utils import trace
from .tcp import TcpNetwork
from .xla import XlaNetwork, drive_rank_threads

__all__ = ["HybridNetwork", "run_spmd_hybrid"]

_XHOST_BIT = 1 << 62
_MAX_TAG = 1 << 32
_MAX_GLOBAL = 1 << 15


_WIN_BAND_CACHE: Optional[Tuple[int, int]] = None


def _win_tag_band() -> Tuple[int, int]:
    """The reserved service tag band — the PARTITIONED-p2p slice plus
    the RMA window-service slice, contiguous by construction in
    comm.py's layout — as (lo, hi). These i64 tags must cross hosts
    for passive-target RMA and partitioned sends to work over the
    hybrid driver, so _compose_tag remaps them reversibly into the TOP
    of the 32-bit composed-tag field. Cached: this sits on the
    per-operation wire path and the service thread's poll loop."""
    global _WIN_BAND_CACHE
    if _WIN_BAND_CACHE is None:
        from ..comm import _WIN_SLICE, _part_tag_base, _win_tag_base

        lo = _part_tag_base()
        hi = _win_tag_base() + _WIN_SLICE
        _WIN_BAND_CACHE = (lo, hi)
    return _WIN_BAND_CACHE


def _compose_tag(src: int, dst: int, tag: int) -> int:
    if tag < 0:
        # Sub-communicator tag regions (mpi_tpu.comm) don't fit the
        # composed cross-host form (ctx + tag + src + dst exceed 64
        # bits); group COLLECTIVES still work hierarchically via
        # group_collectives — only cross-host group p2p is unsupported.
        raise MpiError(
            "mpi_tpu: communicator point-to-point between ranks on "
            "different hosts is not supported by the hybrid driver; use "
            "the communicator's collectives (hierarchical engines) or "
            "world-rank send/receive")
    win_lo, win_hi = _win_tag_band()
    if win_lo <= tag < win_hi:
        # Window-service traffic: same remap on every host and every
        # path (send/receive/iprobe/cancel), so no decomposition is
        # ever needed.
        tag = (_MAX_TAG - (win_hi - win_lo)) + (tag - win_lo)
    elif not 0 <= tag < _MAX_TAG - (win_hi - win_lo):
        raise MpiError(
            f"mpi_tpu: cross-host tags must be in [0, 2**32 - 2**21) "
            f"(the top 2**21 is the partitioned-p2p + RMA "
            f"window-service band), got {tag}")
    return _XHOST_BIT | (src << 47) | (dst << 32) | tag


class HybridNetwork:
    """Backend implementing the :class:`mpi_tpu.api.Interface` SPI across
    hosts. Construct one per host process with the host's TCP identity
    (constructor args or ``--mpi-*`` flags, same ABI as TcpNetwork) and the
    local rank count; run rank threads with :func:`run_spmd_hybrid`."""

    # Communicator (context-region) tags cannot cross hosts — the
    # composed wire tag has no room for a context (_compose_tag).
    # mpi_tpu.comm checks this to route neighborhood collectives through
    # the hierarchical group allgather instead of pairwise sendrecv.
    SUPPORTS_COMM_CROSS_HOST_P2P = False
    # Local ranks are threads sharing one tracer buffer (like the xla
    # driver), so trace collection writes each host process's buffer
    # once via its global-rank-0 thread rather than gathering
    # duplicate per-thread copies. (Cross-host merge of per-host
    # buffers is an observe-layer follow-on; ROADMAP.)
    SHARED_PROCESS_TRACER = True

    def __init__(self, local_ranks: Optional[int] = None,
                 devices: Optional[Sequence[Any]] = None,
                 oversubscribe: bool = True,
                 tcp: Optional[TcpNetwork] = None, **tcp_kwargs: Any):
        self._inner = XlaNetwork(n=local_ranks, devices=devices,
                                 oversubscribe=oversubscribe)
        self._local_n = self._inner.size()
        self._tcp = tcp if tcp is not None else TcpNetwork(**tcp_kwargs)
        self._offsets: List[int] = []        # per-host global-rank offsets
        self._counts: List[int] = []
        self._size = 0
        self._my_offset = 0
        self._init_lock = threading.Lock()
        self._init_done = threading.Event()
        self._init_error: Optional[BaseException] = None
        self._live_ranks = 0  # rank threads inited but not yet finalized
        # Per-communicator hierarchical engines (see group_collectives).
        self._group_colls: "OrderedDict[tuple, _HybridGroupEngine]" = \
            OrderedDict()
        # Cross-host collective tag sequences per (ctx, members): must
        # outlive engine eviction (a rebuilt engine restarting at seq 0
        # while peer hosts kept counting would desync wire tags). Tiny
        # (one int per communicator ever used), so never evicted.
        self._grp_seqs: dict = {}

    # -- rank binding (delegates to the inner xla driver) ---------------------

    def bind_rank(self, local_rank: int) -> None:
        self._inner.bind_rank(local_rank)

    def _local(self) -> int:
        return self._inner.rank()

    # -- topology -------------------------------------------------------------

    def _host_of(self, g: int) -> int:
        for h in range(len(self._offsets)):
            if g < self._offsets[h] + self._counts[h]:
                return h
        raise MpiError(f"mpi_tpu: rank {g} out of range [0, {self._size})")

    # -- Interface ------------------------------------------------------------

    def init(self) -> None:
        """Local xla init barrier; local rank 0 additionally bootstraps the
        host-level TCP mesh and exchanges local-rank counts."""
        self._inner.init()
        if self._local() == 0:
            try:
                self._tcp.init()
                counts = G.allgather(self._tcp, self._local_n)
                self._counts = [int(c) for c in counts]
                self._offsets = []
                off = 0
                for c in self._counts:
                    self._offsets.append(off)
                    off += c
                self._size = off
                self._my_offset = self._offsets[self._tcp.rank()]
                if self._size > _MAX_GLOBAL:
                    raise MpiError(
                        f"mpi_tpu: at most {_MAX_GLOBAL} global ranks "
                        f"supported, got {self._size}")
            except BaseException as exc:  # noqa: BLE001 - re-raised on all
                self._init_error = exc
            finally:
                self._init_done.set()
        else:
            # Track the leader's TCP init timeout (which _use_flags
            # resolves while we wait) rather than a fixed bound; the extra
            # slack covers the count-exchange round after the handshake.
            import time as _time

            start = _time.monotonic()
            while not self._init_done.wait(timeout=1.0):
                limit = (self._tcp.timeout or 120.0) + 60.0
                if _time.monotonic() - start > limit:
                    break
        if self._init_error is not None:
            raise MpiError(
                f"mpi_tpu: hybrid init failed: {self._init_error}"
            ) from self._init_error
        if not self._init_done.is_set():
            raise MpiError("mpi_tpu: hybrid init timed out")
        # Everyone re-syncs so no thread races ahead of the TCP bootstrap.
        self._inner.barrier()
        with self._init_lock:
            self._live_ranks += 1

    def finalize(self) -> None:
        """Refcounted teardown: every local rank thread calls finalize once
        (directly or via the facade); the *last* one — by then every local
        rank has finished communicating — closes the host's TCP mesh.
        Cross-host p2p still in flight at a peer's finalize is a caller
        error, as in the reference (network.go:354-369)."""
        self._inner.finalize()
        with self._init_lock:
            self._live_ranks = max(0, self._live_ranks - 1)
            last = self._live_ranks == 0
        if last:
            self._tcp.finalize()

    def rank(self) -> int:
        return self._my_offset + self._local()

    def size(self) -> int:
        return self._size

    def host_key(self) -> int:
        """Machine identity for ``Comm.split_type("host")``: this host's
        index in the TCP tier, shared by all its local ranks."""
        return self._tcp.rank()

    def _grp_seq_state(self, ctx: int, members: tuple) -> dict:
        """The persistent {lock, seq} record backing a group adapter's
        collective tag sequence. Caller holds no lock; _init_lock guards
        creation (group_collectives already holds it)."""
        key = (int(ctx), tuple(members))
        st = self._grp_seqs.get(key)
        if st is None:
            st = self._grp_seqs[key] = {"lock": threading.Lock(), "seq": 0}
        return st

    def group_collectives(self, members, ctx: int) -> "_HybridGroupEngine":
        """Hierarchical collective engine for a communicator group (the
        mpi_tpu.comm dispatch hook, same contract as
        :meth:`XlaNetwork.group_collectives`): local members share a
        compiled xla sub-mesh engine, host leaders bridge over the TCP
        tier. One shared engine per ``(ctx, members)``."""
        key = (int(ctx), tuple(int(m) for m in members))
        with self._init_lock:
            eng = self._group_colls.get(key)
            if eng is None:
                eng = _HybridGroupEngine(self, key[1], key[0])
                self._group_colls[key] = eng
                while len(self._group_colls) > \
                        XlaNetwork._GROUP_ENGINE_CACHE:
                    self._group_colls.popitem(last=False)
            else:
                self._group_colls.move_to_end(key)
        return eng

    def release_group_collectives(self, members, ctx: int) -> None:
        """Comm.free() hook: drop this group's engine and its inner xla
        engine (compiled programs, filler buffers)."""
        key = (int(ctx), tuple(int(m) for m in members))
        with self._init_lock:
            eng = self._group_colls.pop(key, None)
        if eng is not None:
            self._inner.release_group_collectives(
                tuple(g - self._my_offset for g in eng._local_members),
                key[0])

    # -- point-to-point -------------------------------------------------------

    def send(self, data: Any, dest: int, tag: int) -> None:
        me = self.rank()
        h = self._host_of(dest)
        if h == self._tcp.rank():
            self._inner.send(data, dest - self._my_offset, tag)
        elif trace.enabled():
            # Cross-host (DCN-tier) traffic is the scarce resource the
            # hierarchy exists to conserve — attribute it separately
            # from intra-host hops.
            from ..api import _payload_bytes

            nbytes = _payload_bytes(data)
            trace.count(f"wire.hybrid.tx.bytes.peer{dest}", nbytes)
            with trace.span("hybrid.xhost_send", dest=dest, tag=tag,
                            bytes=nbytes):
                self._tcp.send(data, h, _compose_tag(me, dest, tag))
        else:
            self._tcp.send(data, h, _compose_tag(me, dest, tag))

    def receive(self, source: int, tag: int, out: Optional[Any] = None) -> Any:
        me = self.rank()
        h = self._host_of(source)
        if h == self._tcp.rank():
            return self._inner.receive(source - self._my_offset, tag, out=out)
        if trace.enabled():
            from ..api import _payload_bytes

            with trace.span("hybrid.xhost_recv", source=source, tag=tag):
                result = self._tcp.receive(h, _compose_tag(source, me, tag),
                                           out=out)
            trace.count(f"wire.hybrid.rx.bytes.peer{source}",
                        _payload_bytes(result))
            return result
        return self._tcp.receive(h, _compose_tag(source, me, tag), out=out)

    def cancel_receive(self, source: int, tag: int) -> bool:
        me = self.rank()
        h = self._host_of(source)
        if h == self._tcp.rank():
            return self._inner.cancel_receive(source - self._my_offset, tag)
        return self._tcp.cancel_receive(h, _compose_tag(source, me, tag))

    def iprobe(self, source: int, tag: int) -> bool:
        """Non-consuming MPI_Iprobe across the hierarchy: the inner
        rendezvous for a local peer, the TCP tier (composed tag) for a
        remote one."""
        me = self.rank()
        h = self._host_of(source)
        if h == self._tcp.rank():
            return self._inner.iprobe(source - self._my_offset, tag)
        return self._tcp.iprobe(h, _compose_tag(source, me, tag))

    # -- hierarchical collectives --------------------------------------------
    #
    # The world is just the communicator group (0..size) with identity
    # layout, so every world collective delegates to ONE
    # _HybridGroupEngine over all ranks (uid 0; inner = the world xla
    # engine). The local -> host-leader TCP leg -> local shape, the
    # scatter error envelope, and the reassembly maps therefore exist in
    # exactly one place (the engine), for world and sub-communicators
    # alike. All collectives must be invoked in the same order on every
    # global rank (standard MPI requirement) — that ordering also
    # serialises the leader's TCP legs.

    def _world_engine(self) -> "_HybridGroupEngine":
        if self._size == 0:
            raise MpiError("mpi_tpu: collective before init()")
        with self._init_lock:
            eng = getattr(self, "_world_eng", None)
            if eng is None:
                eng = _HybridGroupEngine(
                    self, tuple(range(self._size)), 0)
                self._world_eng = eng
            return eng

    def allreduce(self, data: Any, op: "OpLike" = "sum") -> Any:
        return self._world_engine().allreduce(data, op=op)

    def reduce(self, data: Any, root: int = 0, op: "OpLike" = "sum") -> Optional[Any]:
        return self._world_engine().reduce(data, root=root, op=op)

    def reduce_scatter(self, data: Any, op: "OpLike" = "sum") -> Any:
        return self._world_engine().reduce_scatter(data, op=op)

    def barrier(self) -> None:
        return self._world_engine().barrier()

    def bcast(self, data: Any, root: int = 0) -> Any:
        return self._world_engine().bcast(data, root=root)

    def allgather(self, data: Any) -> List[Any]:
        return self._world_engine().allgather(data)

    def gather(self, data: Any, root: int = 0) -> Optional[List[Any]]:
        return self._world_engine().gather(data, root=root)

    def scatter(self, data: Optional[List[Any]], root: int = 0) -> Any:
        return self._world_engine().scatter(data, root=root)

    def alltoall(self, data: List[Any]) -> List[Any]:
        return self._world_engine().alltoall(data)


class _TcpGroupAdapter:
    """Host-leader sub-group view of the TCP tier for one communicator's
    hierarchical collectives: rank = index in the participating-host
    list, and collective tags map into an engine-unique block of the far
    negative tag space ``(-2^63, -2^62]`` — disjoint from user tags,
    world collective tags (>= 2^48), cross-host composed tags (bit 62),
    and Comm context regions (> -2^62). ``uid`` must be unique among
    engines that can share a host link: ``ctx * 2^15 + min(members)``
    is, because comms sharing a context are disjoint (split siblings),
    so their lowest members differ. The collective tag sequence
    (``_coll_seq``, advanced by ``collectives_generic``) is CROSS-HOST
    state — every participating host's leader must be at the same
    sequence — so it lives in ``seq_state`` (a per-(ctx, members) dict
    owned by the driver) and survives engine eviction/rebuild.
    Offsets wrap modulo ``_BLOCK``: collectives on one communicator are
    globally ordered and tags are released on completion, so a wrapped
    offset can only collide with itself 2^17 collectives later."""

    # uid < 2^33 (max ctx 2^18-1) and uid * _BLOCK + off must stay
    # within (-2^63, -2^62]: 2^33 * 2^29 == 2^62 exactly.
    _BLOCK = 1 << 29

    def __init__(self, tcp: TcpNetwork, hosts: List[int], uid: int,
                 seq_state: dict):
        if not 0 <= uid < (1 << 33):
            raise MpiError(f"mpi_tpu: group-engine uid {uid} out of range")
        self._tcp = tcp
        self._hosts = list(hosts)
        self._uid = uid
        self._seq_state = seq_state

    # collectives_generic._next_tag_base reads/writes these on the impl
    # it is handed; proxy to the driver-owned state so a rebuilt adapter
    # continues the sequence its cross-host peers are at.
    @property
    def _coll_lock(self) -> threading.Lock:
        return self._seq_state["lock"]

    @property
    def _coll_seq(self) -> int:
        return self._seq_state["seq"]

    @_coll_seq.setter
    def _coll_seq(self, value: int) -> None:
        self._seq_state["seq"] = value

    def rank(self) -> int:
        return self._hosts.index(self._tcp.rank())

    def size(self) -> int:
        return len(self._hosts)

    def _map(self, tag: int) -> int:
        off = (tag - G.COLL_TAG_BASE) % self._BLOCK
        return -(1 << 62) - self._uid * self._BLOCK - off - 1

    def send(self, data: Any, dest: int, tag: int) -> None:
        self._tcp.send(data, self._hosts[dest], self._map(tag))

    def receive(self, source: int, tag: int, out: Optional[Any] = None) -> Any:
        return self._tcp.receive(self._hosts[source], self._map(tag), out=out)

    def cancel_receive(self, source: int, tag: int) -> bool:
        return self._tcp.cancel_receive(self._hosts[source], self._map(tag))


class _HybridGroupEngine:
    """Hierarchical collectives for one communicator over the hybrid
    driver: local members run the xla driver's compiled sub-mesh engine,
    host leaders (first local member in group order) bridge hosts over
    the TCP tier, and results fan back out through the local engine —
    the same local → leader-leg → local shape as the world collectives,
    with explicit group-rank maps because a key-permuted split need not
    keep hosts contiguous. The full suite is defined here except
    scan/exscan, whose generic algorithms ride :meth:`allgather` (via
    ``collectives_generic._allgather_best`` on the Comm) — never
    cross-host p2p, which the hybrid driver rejects for communicator
    tags."""

    def __init__(self, net: "HybridNetwork", members: tuple, ctx: int):
        self._net = net
        self._members = tuple(members)
        h = net._tcp.rank()
        self._local_members = [g for g in self._members
                               if net._host_of(g) == h]
        if not self._local_members:
            raise MpiError(
                "mpi_tpu: hybrid group engine built on a host with no "
                "group members")
        self._hosts = sorted({net._host_of(g) for g in self._members})
        local_ranks = tuple(g - net._my_offset for g in self._local_members)
        if local_ranks == tuple(range(net._local_n)):
            # Full local membership in natural order: the driver's world
            # xla engine IS this group's inner engine (don't duplicate
            # its jit cache / rendezvous barrier).
            self._inner = net._inner
        else:
            self._inner = net._inner.group_collectives(local_ranks, ctx)
        self._tcp_grp = _TcpGroupAdapter(
            net._tcp, self._hosts, ctx * _MAX_GLOBAL + min(self._members),
            net._grp_seq_state(ctx, self._members))
        # group rank of each local member, in local (inner) order
        self._local_granks = [self._members.index(g)
                              for g in self._local_members]

    # -- helpers -----------------------------------------------------------

    def _is_leader(self) -> bool:
        return self._net.rank() == self._local_members[0]

    def _leader_leg(self, local_result: Any, leg: Callable[[Any], Any],
                    span_prefix: str = "") -> Any:
        """Leader bridges hosts, result fans back out locally. With
        ``span_prefix`` set, each phase records a trace span:
        ``<p>.leader_exchange`` and ``<p>.local_bcast`` on the leader
        (separately attributable costs — the leader enters its bcast
        only after its exchange, so its bcast span is pure fan-out
        work), ``<p>.follower_wait`` on non-leaders (their bcast entry
        blocks until the leader finishes the exchange, so the wait
        covers both phases and is named as such rather than
        masquerading as bcast cost)."""
        if len(self._hosts) == 1:
            return local_result
        if not span_prefix:
            out = leg(local_result) if self._is_leader() else None
            return self._inner.bcast(out, root=0)
        if self._is_leader():
            with trace.span(f"{span_prefix}.leader_exchange"):
                out = leg(local_result)
            with trace.span(f"{span_prefix}.local_bcast"):
                return self._inner.bcast(out, root=0)
        with trace.span(f"{span_prefix}.follower_wait"):
            return self._inner.bcast(None, root=0)

    # -- collectives -------------------------------------------------------

    # Large allreduces CAN pipeline the two leader-leg tiers — the
    # 1 MiB x 32-rank tier split shows exchange (~14 ms) and bcast
    # (~7 ms leader-side; followers wait out both, ~21 ms) fully
    # serialized on the critical path, and on a real
    # multi-host fabric they use different resources (NIC vs local
    # memory), so overlap should approach max() of the tiers.
    #
    # EXPERIMENTAL, DCN-ONLY (round-5 verdict #4 resolution): the
    # gate ships CLOSED and this lever must not be enabled on any
    # fabric without winning its own A/B there. The definitive
    # loopback measurement (16/64 MiB, 4+8 chunks, interleaved
    # variants on the zero-copy wire path — docs/PERF_NOTES.md) shows
    # 0.83x-1.05x, inside the serial leg's rerun spread: one core has
    # nothing to overlap. Enable on a real multi-host deployment with
    # MPI_TPU_HYBRID_PIPELINE_MIN=<bytes> after an on-fabric A/B.
    _PIPELINE_CHUNKS = 4

    @staticmethod
    def _pipeline_min_bytes() -> int:
        import os as _os

        try:
            return int(_os.environ.get("MPI_TPU_HYBRID_PIPELINE_MIN",
                                       str(1 << 62)))
        except ValueError:
            return 1 << 62

    @classmethod
    def _pipeline_eligible(cls, nbytes: int) -> bool:
        """Engage window: [threshold, RING_MIN_BYTES). The upper cap
        is a CORRECTNESS bound, not tuning: binomial-tree reduction is
        elementwise-association-invariant under chunking (chunk
        results equal the whole-buffer tree bitwise), but at ring
        sizes the serial leg switches to ring order whose per-element
        association depends on block boundaries — chunked rings would
        diverge bitwise from the whole-buffer path and break the
        cross-driver parity contract (collectives_generic.
        ring_eligible). Above the cap the ring is already the
        bandwidth-optimal leg; the pipeline's domain is the mid-size
        regime."""
        return (cls._pipeline_min_bytes() <= nbytes
                < G.RING_MIN_BYTES)

    def _pipelined_leader_leg(self, total, op) -> Any:
        """Chunked overlap of the leader leg's two serial tiers: the
        leader runs the per-chunk TCP exchange in a producer thread
        while the main thread broadcasts each exchanged chunk locally
        — chunk i's exchange rides UNDER chunk i-1's bcast, so the
        critical path approaches max(exchange, bcast) + one chunk
        instead of their sum. Deterministic chunking (np.array_split
        on the flat buffer) keeps every rank's bcast sequence
        identical; the producer is the only _tcp_grp user while it
        runs, so the leader tier's collective ordering is unchanged."""
        import numpy as np

        with trace.span("hybrid.allreduce.pipelined",
                        nbytes=int(total.nbytes)):
            shape, dtype = total.shape, total.dtype
            chunks = np.array_split(total.reshape(-1),
                                    self._PIPELINE_CHUNKS)
            if self._is_leader():
                import queue

                done: "queue.Queue" = queue.Queue()

                def producer() -> None:
                    try:
                        for ch in chunks:
                            done.put(G.allreduce(self._tcp_grp,
                                                 np.ascontiguousarray(ch),
                                                 op=op))
                    except BaseException as exc:  # noqa: BLE001
                        done.put(exc)  # surfaced by the consumer below

                th = threading.Thread(target=producer, daemon=True,
                                      name="hybrid-pipeline-exchange")
                th.start()
                out = []
                for _ in chunks:
                    item = done.get()
                    if isinstance(item, BaseException):
                        # Every local rank still gets its bcast (the
                        # exception travels), so the failure raises on
                        # the whole host instead of deadlocking it.
                        self._inner.bcast(item, root=0)
                        th.join()
                        raise item
                    out.append(self._inner.bcast(item, root=0))
                th.join()
            else:
                out = []
                for _ in chunks:
                    item = self._inner.bcast(None, root=0)
                    if isinstance(item, BaseException):
                        raise item
                    out.append(item)
            return np.concatenate(out).astype(dtype,
                                              copy=False).reshape(shape)

    def allreduce(self, data: Any, op="sum") -> Any:
        G.check_op(op)
        if callable(op):
            # User callables promise associativity only — the
            # hierarchical local-then-host fold would reorder operands
            # whenever group order interleaves hosts, silently breaking
            # non-commutative ops. allgather is group-rank-ordered, so
            # fold it in the canonical tree instead (same order as every
            # other driver).
            return G.tree_combine(self.allgather(data), op)
        # One trace span per tier (see _leader_leg): the phases hide
        # behind one opaque latency otherwise, and a regression in the
        # DCN-analogue leader tier would be indistinguishable from
        # local noise (bench reads these spans; span() is a one-bool
        # check when tracing is off).
        import jax
        import numpy as np

        with trace.span("hybrid.allreduce.local_reduce"):
            local_total = self._inner.allreduce(data, op=op)
            # The xla driver hands a device payload's result back on the
            # device; this driver's results are host arrays (the leader
            # leg sends them over sockets), so read it once, here.
            if isinstance(local_total, jax.Array):
                local_total = np.asarray(local_total)

        if len(self._hosts) > 1 \
                and isinstance(local_total, np.ndarray) \
                and self._pipeline_eligible(local_total.nbytes):
            return self._pipelined_leader_leg(local_total, op)
        return self._leader_leg(
            local_total, lambda t: G.allreduce(self._tcp_grp, t, op=op),
            span_prefix="hybrid.allreduce")

    def reduce(self, data: Any, root: int = 0, op: "OpLike" = "sum"
               ) -> Optional[Any]:
        result = self.allreduce(data, op=op)
        me = self._members.index(self._net.rank())
        return result if me == root else None

    def barrier(self) -> None:
        self._inner.barrier()
        if self._is_leader() and len(self._hosts) > 1:
            G.barrier(self._tcp_grp)
        self._inner.barrier()

    def bcast(self, data: Any, root: int = 0) -> Any:
        g_root = self._members[root]
        root_host = self._net._host_of(g_root)
        if root_host == self._net._tcp.rank():
            payload = self._inner.bcast(
                data, root=self._local_members.index(g_root))
            if self._is_leader() and len(self._hosts) > 1:
                G.bcast(self._tcp_grp, payload,
                        root=self._hosts.index(root_host))
            return payload
        payload = None
        if self._is_leader():
            payload = G.bcast(self._tcp_grp, None,
                              root=self._hosts.index(root_host))
        return self._inner.bcast(payload, root=0)

    def allgather(self, data: Any) -> List[Any]:
        locals_ = self._inner.allgather(data)

        def leg(locals_list: List[Any]) -> List[Any]:
            # Tag each payload with its group rank: a key-permuted split
            # can interleave hosts arbitrarily in group order.
            tagged = list(zip(self._local_granks, locals_list))
            per_host = G.allgather(self._tcp_grp, tagged)
            flat = [p for chunk in per_host for p in chunk]
            flat.sort(key=lambda e: e[0])
            return [p for _, p in flat]

        return self._leader_leg(locals_, leg)

    def gather(self, data: Any, root: int = 0) -> Optional[List[Any]]:
        result = self.allgather(data)
        me = self._members.index(self._net.rank())
        return result if me == root else None

    def reduce_scatter(self, data: Any, op: "OpLike" = "sum") -> Any:
        """Hierarchical allreduce, then keep this group rank's block."""
        import numpy as _np

        n = len(self._members)
        arr = _np.asarray(data)
        if arr.ndim < 1 or arr.shape[0] % n:
            raise MpiError(
                f"mpi_tpu: reduce_scatter payload leading axis "
                f"{arr.shape if arr.ndim else 'scalar'} must divide into "
                f"{n} equal blocks")
        total = _np.asarray(self.allreduce(data, op=op))
        m = arr.shape[0] // n
        me = self._members.index(self._net.rank())
        return total[me * m:(me + 1) * m]

    def _host_chunk(self, items: List[Any], host: int) -> List[Any]:
        """items (ordered by group rank) restricted to ``host``'s members,
        in that host's local (inner) order."""
        return [items[gr] for gr, g in enumerate(self._members)
                if self._net._host_of(g) == host]

    def scatter(self, data: Optional[List[Any]], root: int = 0) -> Any:
        """Root's per-group-rank list → one inner gather hop to root's
        host leader, per-host chunks over TCP, local scatter. The TCP
        leg carries a (status, payload) envelope so a bad list raises on
        every member instead of deadlocking (same shape as the world
        scatter)."""
        n = len(self._members)
        g_root = self._members[root]
        root_host = self._net._host_of(g_root)
        multi = len(self._hosts) > 1
        chunk = None
        error = None
        if root_host == self._net._tcp.rank():
            gathered = self._inner.gather(
                data, root=0)  # leader collects local members' args
            items = None
            if self._is_leader():
                items = gathered[self._local_members.index(g_root)]
                if items is None or len(items) != n:
                    error = (f"mpi_tpu: scatter root needs a list of "
                             f"exactly {n} payloads")
                if multi:
                    if error is not None:
                        envelopes = [("err", error)] * len(self._hosts)
                    else:
                        envelopes = [("ok", self._host_chunk(items, hh))
                                     for hh in self._hosts]
                    G.scatter(self._tcp_grp, envelopes,
                              root=self._hosts.index(root_host))
                if error is None:
                    chunk = self._host_chunk(items, root_host)
        else:
            if self._is_leader():
                status, payload = G.scatter(
                    self._tcp_grp, None, root=self._hosts.index(root_host))
                if status == "err":
                    error = payload
                else:
                    chunk = payload
        error = self._inner.bcast(error, root=0)
        if error is not None:
            raise MpiError(error)
        return self._inner.scatter(chunk, root=0)

    def alltoall(self, data: List[Any]) -> List[Any]:
        """Rows to host bundles over TCP, reassembled per local member in
        group-rank order (world alltoall generalized to non-contiguous
        group layouts)."""
        n = len(self._members)
        if len(data) != n:
            raise MpiError(
                f"mpi_tpu: alltoall needs exactly {n} payloads, got "
                f"{len(data)}")
        rows = self._inner.allgather(data)  # [local idx] -> n-list
        if len(self._hosts) == 1:
            me_local = self._local_members.index(self._net.rank())
            my_g = self._local_granks[me_local]
            return [row[my_g] for row in rows]

        def leg(rows_: List[List[Any]]) -> Optional[List[List[Any]]]:
            # bundles[h] = (src group ranks here, rows sliced to h's
            # members); sources are tagged so the receiver can reorder.
            bundles = []
            for hh in self._hosts:
                dst_granks = [gr for gr, g in enumerate(self._members)
                              if self._net._host_of(g) == hh]
                bundles.append([
                    (src_g, [row[d] for d in dst_granks])
                    for src_g, row in zip(self._local_granks, rows_)
                ])
            received = G.alltoall(self._tcp_grp, bundles)
            # received[h] = list of (src_grank, payloads-for-my-members)
            per_src: List[tuple] = sorted(
                (entry for chunk in received for entry in chunk),
                key=lambda e: e[0])
            out_rows = []
            for li in range(len(self._local_members)):
                out_rows.append([payloads[li] for _, payloads in per_src])
            return out_rows

        out_rows = leg(rows) if self._is_leader() else None
        return self._inner.scatter(out_rows, root=0)


def run_spmd_hybrid(fn: Callable[[], Any], net: HybridNetwork,
                    register_facade: bool = True) -> List[Any]:
    """Run ``fn`` on one thread per *local* rank of this host — the
    per-host analogue of :func:`mpi_tpu.backends.xla.run_spmd`; the
    launcher starts one such process per host (same flag ABI as the TCP
    driver, gompirun.go:28-93)."""

    def abort() -> None:
        net._inner._init_barrier.abort()
        net._inner.abort_collectives()
        net._init_done.set()

    def on_failure() -> None:
        # Ranks that errored never reach finalize, so the refcount never
        # drains — close the host TCP mesh here or the listener socket and
        # reader threads leak past the failed run.
        try:
            net._tcp.finalize()
        except Exception:  # noqa: BLE001 - best-effort teardown
            pass

    return drive_rank_threads(
        fn, nranks=net._inner.size(), bind=net.bind_rank, abort=abort,
        inherit_net=net._inner, facade_net=net, name_prefix="mpi-hybrid",
        register_facade=register_facade, on_failure=on_failure)
