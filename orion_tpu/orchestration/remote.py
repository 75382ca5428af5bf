"""Cross-process channel + elastic rollout-worker pool for the
decoupled rollout/learner split.

JAX on multi-host pods is multi-controller for GLOBAL-mesh programs —
every process must execute the same program over the same devices.  A
decoupled async split (SURVEY.md §3b: rollout group and learner group
running DIFFERENT programs at their own cadence) therefore cannot put
both groups in one mesh; instead each process group drives a mesh of
its LOCAL devices only, and the two things that cross the process
boundary travel host-side:

- trajectory batches (rollout → learner): ``GenerationResult`` fields
  + scores as numpy,
- weight snapshots (learner → rollout): the param tree as numpy,
  version-tagged for the staleness gate.

This is the DCN-through-host hop every decoupled RLHF stack has (the
reference's rollout workers feed the learner through an object store /
parameter channel the same way); XLA collectives still carry all
INTRA-group traffic over ICI.  ``tests/test_multihost.py::
test_two_process_async_decoupled`` runs the 1×1 pattern on two real
processes; ``tests/test_worker_pool.py`` runs the N-worker pool.

Wire format: a fixed header — magic bytes, protocol version, frame
kind, the sender's (trace id, span id) — then a length-prefixed
pickle of a numpy pytree.  A stray or version-skewed peer fails the
handshake with a clear :class:`ProtocolError` instead of an opaque
pickle exception mid-run.  The trace ids are the distributed-tracing
hook (orion_tpu.obs): the HELLO ack carries the learner's trace id,
every worker adopts it, and TRAJ frames name the worker's generate
span — so one trace stitches submit → worker-generate → TRAJ →
consume → update across the whole pool, and per-process Chrome dumps
merge into a single Perfetto timeline.
Pickle is safe here: both endpoints are processes of the same training
job on a private port, which is the same trust domain as the
checkpoint files they already exchange.

The pool layer (SURVEY.md §5 "failure detection / elastic recovery",
ROADMAP open item 1) generalizes the 1×1 split:

- :class:`WorkerPool` — the learner side: an accept loop admits N
  rollout processes mid-run (join / leave / rejoin), one receive
  thread per worker demultiplexes HEARTBEAT / TRAJ / GOODBYE frames,
  per-worker queues keep the consumption order deterministic
  (round-robin), weight broadcast fans one shared WEIGHTS payload out
  with version tags, and each consumed item sends a tiny ACK frame
  back — the per-worker backpressure signal the client-side capacity
  gate runs on.  Missed heartbeats or a dropped socket mark
  a worker dead; a crashed worker's queued (in-flight) batches are
  DISCARDED — a torn trajectory must never be donated to the
  optimizer — while a GOODBYE'd worker's backlog stays consumable.
- :class:`PoolWorkerClient` — the rollout-process side: HELLO
  handshake, a heartbeat sender thread, latest-wins weight reception,
  and :meth:`PoolWorkerClient.run` — the generation loop every worker
  process (or thread standing in for one, in tests) drives.
"""

from __future__ import annotations

import logging
import os
import pickle
import queue
import random
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from orion_tpu import obs
from orion_tpu.resilience import Watchdog, fault_point

_LOG = logging.getLogger(__name__)

_LEN = struct.Struct(">Q")

# ---------------------------------------------------------------------------
# frame protocol
# ---------------------------------------------------------------------------

#: Channel magic: the first bytes of EVERY message.  A peer that is not
#: an orion pytree channel (a health checker, a port scanner, an old
#: build) fails loudly at the first frame instead of feeding garbage
#: lengths into the pickle loader.
MAGIC = b"ORTP"
#: Bumped on any wire-format change; both ends must match exactly.
#: v4: the header grew trace/span ids (distributed tracing — one
#: trace id stitches learner + every worker into a single Perfetto
#: timeline); a v3 peer is rejected cleanly by the version check.
#: v5: the serving-gateway frame family (FRAME_SUBMIT / FRAME_STREAM /
#: FRAME_CANCEL, defined in orchestration/gateway.py) joined the
#: channel — the header itself is unchanged, but a v4 peer predates
#: those kinds and must be rejected at the handshake, not when the
#: first unknown frame arrives mid-stream.
#: v6: the prefill-tier KV handoff family (FRAME_KV_OFFER /
#: FRAME_KV_PAGES / FRAME_KV_ACK, defined in
#: orchestration/prefill_tier.py) joined the channel — again no
#: header change, but a v5 peer must be turned away at HELLO, not
#: when a KV_PAGES frame (megabytes of paged KV) lands on a peer
#: that cannot dispatch it.
#: v7: FRAME_WEIGHTS_ACK joined the pool family and WEIGHTS grew the
#: two-phase staged/commit/abort push (zero-downtime fleet rollout) —
#: header unchanged, but a v6 worker neither ACKs weights nor
#: understands a staged snapshot, so a skewed peer must be rejected
#: at HELLO, not discovered when the commit point times out.
#: v8: the replica-edge membership family (FRAME_REPLICA_HB /
#: FRAME_EDGE, defined in orchestration/replica.py) joined the
#: channel — gateway replicas heartbeat each other and push the live
#: edge set to their clients.  Header unchanged, but a v7 peer
#: predates replica HELLOs and edge pushes, so a skewed gateway must
#: be turned away at the handshake, not when the first membership
#: frame lands on a peer that cannot dispatch it.
PROTOCOL_VERSION = 8

#: magic(4) + version(u16) + kind(u8) + trace id(u64) + originating
#: span id(u64) + payload length(u64).  The trace/span ids are 0 when
#: the sender's tracer is disabled — tracing changes no wire SIZE,
#: only two header fields.
_HEADER = struct.Struct(">4sHBQQQ")

#: Wire-format history: PROTOCOL_VERSION -> the header pack format it
#: shipped with.  The ``frame-exhaustive`` analysis rule enforces that
#: the CURRENT format is registered under the CURRENT version — so any
#: edit to ``_HEADER`` fails the gate until PROTOCOL_VERSION is bumped
#: and a new entry appended (the machine-checked form of the PR 9
#: v3→v4 rule: a pack-format change IS a wire-format change, and a
#: skewed peer must fail the version check, not the pickle loader).
_HEADER_HISTORY = {
    3: ">4sHBQ",     # PR 6: magic + version + kind + length
    4: ">4sHBQQQ",   # PR 9: + trace id + span id (distributed tracing)
    5: ">4sHBQQQ",   # PR 12: same header; gateway frame family added
    6: ">4sHBQQQ",   # PR 17: same header; prefill-tier KV family added
    7: ">4sHBQQQ",   # PR 18: same header; WEIGHTS_ACK/commit handshake
    8: ">4sHBQQQ",   # PR 20: same header; replica-edge membership family
}

# Frame kinds multiplexed on one channel.
FRAME_DATA = 0        # legacy send()/recv() payload
FRAME_HELLO = 1       # worker → learner admission; learner → worker ack
FRAME_HEARTBEAT = 2   # worker → learner liveness
FRAME_TRAJ = 3        # worker → learner trajectory batch
FRAME_WEIGHTS = 4     # learner → worker: version-tagged param snapshot
                      # (plain install, or staged/commit/abort — v7)
FRAME_GOODBYE = 5     # either side: graceful leave (≠ crash)
FRAME_ACK = 6         # learner → worker: consumed-count (backpressure)
FRAME_WEIGHTS_ACK = 7  # worker → learner: weight version staged/applied

_FRAME_NAMES = {
    FRAME_DATA: "DATA", FRAME_HELLO: "HELLO",
    FRAME_HEARTBEAT: "HEARTBEAT", FRAME_TRAJ: "TRAJ",
    FRAME_WEIGHTS: "WEIGHTS", FRAME_GOODBYE: "GOODBYE",
    FRAME_ACK: "ACK", FRAME_WEIGHTS_ACK: "WEIGHTS_ACK",
}


class ProtocolError(ConnectionError):
    """The peer is not speaking this channel's protocol (bad magic) or
    speaks a different version of it.  Deliberately a ConnectionError
    subclass: supervisors treat a protocol-confused peer like any other
    broken connection — drop it, keep the pool alive."""


def host_tree(tree: Any) -> Any:
    """Numpy copy of a jax pytree via ONE batched device→host
    transfer (per-leaf ``np.asarray`` would block on one device
    round trip per leaf)."""
    return jax.tree.map(np.asarray, jax.device_get(tree))


def _harden_socket(sock: socket.socket) -> None:
    """TCP_NODELAY + SO_KEEPALIVE (+ aggressive keepalive knobs where
    the platform exposes them).  Without keepalive, a peer host that
    dies silently (power loss, network partition — no FIN/RST) leaves
    ``recv()`` blocked FOREVER; with it the kernel probes the idle
    connection and surfaces an error in minutes instead of never."""
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_KEEPALIVE, 1)
    for opt, val in (("TCP_KEEPIDLE", 60), ("TCP_KEEPINTVL", 10),
                     ("TCP_KEEPCNT", 6)):
        if hasattr(socket, opt):  # linux; darwin lacks KEEPIDLE
            try:
                sock.setsockopt(socket.IPPROTO_TCP,
                                getattr(socket, opt), val)
            except OSError:  # pragma: no cover - platform-dependent
                pass
    # Kernel-level send deadline (direction-specific, so a concurrent
    # recv is untouched): a live-but-not-draining peer — SIGSTOPped
    # process, dead receiver thread — fills its TCP buffer and would
    # otherwise block the learner's weight broadcast in sendall()
    # FOREVER.  Per-syscall: a slow peer that keeps draining resets
    # the clock; only zero progress for the full window errors out.
    if hasattr(socket, "SO_SNDTIMEO"):
        try:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDTIMEO,
                            struct.pack("ll", 300, 0))
        except OSError:  # pragma: no cover - platform-dependent
            pass


def listen_socket(port: int, host: str = "localhost", backlog: int = 16,
                  accept_timeout: float = 0.5) -> socket.socket:
    """A configured listening TCP socket for a frame-channel accept
    loop.  ALL raw socket creation stays in this module (the
    ``raw-socket`` analysis rule): WorkerPool and the serving gateway
    both accept peers through sockets built here, and every accepted
    connection is immediately wrapped in :class:`PyTreeChannel` —
    nothing outside this file speaks unframed bytes."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind((host, port))
    srv.listen(backlog)
    srv.settimeout(accept_timeout)
    return srv


class PyTreeChannel:
    """Blocking point-to-point pytree channel over TCP.

    ``recv_deadline`` (seconds, 0 = block forever): an idle-receive
    deadline — a ``recv`` that sees no bytes for this long raises
    :class:`TimeoutError` instead of hanging the learner on a silently
    dead peer.  Sends are serialized by an internal lock so a
    heartbeat thread and a trajectory sender can share the channel.

    Tracing: every frame header carries the sender's
    (trace id, current span id) — ``tracer`` defaults to the process
    tracer (``orion_tpu.obs``); tests standing in for several
    processes inside one interpreter pass per-endpoint instances.
    After a ``recv_frame``, ``last_remote_ctx`` holds the peer's ids
    (the worker adopts the learner's trace id from it; the learner
    links consume events to the worker's generate span).
    """

    def __init__(self, sock: socket.socket, recv_deadline: float = 0.0,
                 tracer=None):
        self._sock = sock
        _harden_socket(sock)
        self._send_lock = threading.Lock()
        self._tracer = tracer
        self.last_remote_ctx: Tuple[int, int] = (0, 0)
        sock.settimeout(None)  # blocking; deadlines are kernel-level
        self.set_recv_deadline(recv_deadline)

    def _trc(self):
        return self._tracer if self._tracer is not None else \
            obs.get_tracer()

    def set_recv_deadline(self, deadline: float) -> None:
        """Apply the idle-receive deadline via SO_RCVTIMEO — kernel-
        level and DIRECTION-SPECIFIC, never ``settimeout()``: Python's
        socket timeout caps the total duration of ``sendall`` too, so
        a 30s receive deadline would also abort any weights send
        slower than 30s and falsely mark a healthy peer dead.  The
        send direction has its own progress deadline (SO_SNDTIMEO in
        ``_harden_socket``)."""
        self.recv_deadline = max(float(deadline), 0.0)
        sec = int(self.recv_deadline)
        usec = int((self.recv_deadline - sec) * 1e6)
        try:
            self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVTIMEO,
                                  struct.pack("ll", sec, usec))
        except OSError:  # pragma: no cover - platform-dependent
            # Fallback to the bidirectional Python timeout: a capped
            # send beats an unbounded hang on a dead peer.
            self._sock.settimeout(self.recv_deadline or None)

    @classmethod
    def listen(cls, port: int, host: str = "localhost",
               timeout: float = 120.0,
               recv_deadline: float = 0.0, tracer=None) -> "PyTreeChannel":
        """Accept exactly one peer (the 1×1 split; the pool uses
        :class:`WorkerPool` instead)."""
        srv = socket.socket()
        srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        srv.bind((host, port))
        srv.listen(1)
        srv.settimeout(timeout)
        try:
            conn, _ = srv.accept()
        finally:
            srv.close()
        return cls(conn, recv_deadline=recv_deadline, tracer=tracer)

    @classmethod
    def connect(cls, port: int, host: str = "localhost",
                timeout: float = 120.0,
                seed: Optional[int] = None,
                recv_deadline: float = 0.0,
                tracer=None) -> "PyTreeChannel":
        """Connect to the listening peer, retrying until it is up.

        Jittered exponential backoff: a fixed retry cadence from every
        rollout process makes the listener's accept queue a thundering
        herd on restart.  The jitter stream seeds from the PID by
        default, so co-restarting processes desynchronize with no
        caller plumbing; pass ``seed`` (e.g. the process rank) for a
        deterministic schedule instead.  On deadline the TimeoutError
        carries the *last* socket error — a bare timeout hides whether
        the peer was down (ConnectionRefused) or the address was wrong
        (NoRouteToHost)."""
        deadline = time.monotonic() + timeout
        rng = random.Random(os.getpid() if seed is None else seed)
        delay = 0.05
        last: Optional[OSError] = None
        while True:
            try:
                sock = socket.create_connection((host, port),
                                                timeout=timeout)
                # The timeout above governs only connection setup; the
                # channel's own recv_deadline (0 = block forever — a
                # learner can legitimately spend minutes inside one
                # compile) takes over from here, with SO_KEEPALIVE
                # guarding the silent-peer-death case either way.
                return cls(sock, recv_deadline=recv_deadline,
                           tracer=tracer)
            except OSError as e:
                last = e
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"PyTreeChannel.connect({host}:{port}) gave up "
                        f"after {timeout:.1f}s; last socket error: "
                        f"{last!r}") from last
                time.sleep(min(delay * (1.0 + 0.25 * rng.random()),
                               remaining))
                delay = min(delay * 2.0, 2.0)

    # -- framed sends/receives -----------------------------------------
    def send_frame(self, kind: int, tree: Any) -> None:
        self.send_raw(kind, pickle.dumps(
            tree, protocol=pickle.HIGHEST_PROTOCOL))

    def send_raw(self, kind: int, payload: bytes) -> None:
        """Send an already-pickled payload.  ``WorkerPool.broadcast``
        serializes the (identical, multi-GB) weights snapshot ONCE and
        fans the shared bytes out through this — re-pickling per
        worker would cost N full serializations of the same tree on
        the learner's critical path."""
        fault_point("remote.channel")
        tr = self._trc()
        tid, sid = tr.context()  # (0, 0) when tracing is off
        # Header and payload go out separately: concatenating would
        # materialize a second full copy of a multi-GB weight snapshot.
        with self._send_lock:
            self._sock.sendall(_HEADER.pack(MAGIC, PROTOCOL_VERSION,
                                            kind, tid, sid, len(payload)))
            self._sock.sendall(payload)
        if tr.enabled:
            tr.instant("ortp.send." + _FRAME_NAMES.get(kind, str(kind)),
                       bytes=len(payload))

    def recv_frame(self) -> Tuple[int, Any]:
        fault_point("remote.channel")
        magic, version, kind, r_tid, r_sid, n = _HEADER.unpack(
            self._recv_exact(_HEADER.size))
        if magic != MAGIC:
            raise ProtocolError(
                f"pytree channel peer sent bad magic {magic!r} "
                f"(want {MAGIC!r}): not an orion channel peer")
        if version != PROTOCOL_VERSION:
            raise ProtocolError(
                f"pytree channel protocol version mismatch: peer "
                f"speaks v{version}, this build speaks "
                f"v{PROTOCOL_VERSION} — mixed-build job?")
        buf = bytearray(n)
        view = memoryview(buf)
        got = 0
        while got < n:
            try:
                r = self._sock.recv_into(view[got:])
            except (socket.timeout, BlockingIOError):
                # SO_RCVTIMEO elapsed surfaces as EAGAIN
                # (BlockingIOError); the settimeout fallback raises
                # socket.timeout.
                raise TimeoutError(
                    f"pytree channel recv idle past "
                    f"{self.recv_deadline:.1f}s mid-message "
                    f"(peer hung?)") from None
            if not r:
                raise ConnectionError(
                    "pytree channel peer closed mid-message")
            got += r
        # The peer's tracing context: the caller decides what to do
        # with it (workers ADOPT the learner's trace id; the learner
        # links consume events to the worker's generate span).
        self.last_remote_ctx = (r_tid, r_sid)
        tr = self._trc()
        if tr.enabled:
            tr.instant("ortp.recv." + _FRAME_NAMES.get(kind, str(kind)),
                       parent=r_sid, bytes=n)
        return kind, pickle.loads(view)

    # -- legacy unframed API (kind DATA) --------------------------------
    def send(self, tree: Any) -> None:
        self.send_frame(FRAME_DATA, tree)

    def recv(self) -> Any:
        # Kind is intentionally ignored: 1×1-split callers pair their
        # own sends/receives and never multiplex frame kinds.
        return self.recv_frame()[1]

    def _recv_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            try:
                chunk = self._sock.recv(n - len(buf))
            except (socket.timeout, BlockingIOError):
                raise TimeoutError(
                    f"pytree channel recv idle past "
                    f"{self.recv_deadline:.1f}s (peer alive but "
                    "silent; raise recv_deadline if this learner "
                    "legitimately blocks this long)") from None
            if not chunk:
                raise ConnectionError(
                    "pytree channel peer closed mid-message")
            buf.extend(chunk)
        return bytes(buf)

    def close(self) -> None:
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()


# ---------------------------------------------------------------------------
# learner side: the elastic worker pool
# ---------------------------------------------------------------------------


class PoolMember:
    """Learner-side record of one admitted rollout worker."""

    def __init__(self, wid: int, name: str, chan: PyTreeChannel, hb):
        self.wid = wid
        self.name = name
        self.chan = chan
        self.hb = hb                      # resilience.Heartbeat
        self.queue: queue.Queue = queue.Queue()
        self.version = -1                 # last WEIGHTS version sent
        self.staged_version = -1          # WEIGHTS_ACK'd as staged
        self.acked_version = -1           # WEIGHTS_ACK'd as applied
        self.alive = True
        self.left = False                 # GOODBYE received (graceful)
        self.produced = 0                 # TRAJ frames received
        self.consumed = 0                 # items handed to the learner
        self.thread: Optional[threading.Thread] = None


class WorkerPool:
    """Supervised accept loop + per-worker channels for N rollout
    processes (ROADMAP open item 1: elastic membership).

    Liveness has three layers, cheapest first: a dropped socket marks
    the worker dead immediately (its receive thread sees EOF); missed
    heartbeats past ``heartbeat_timeout`` mark a live-but-wedged worker
    dead on the next :meth:`reap_stalled` poll; SO_KEEPALIVE (set on
    every channel) bounds the silent-host-death case.  A dead worker's
    QUEUED batches are discarded — its in-flight trajectory must never
    be donated to the optimizer — while a worker that said GOODBYE
    keeps its backlog consumable (graceful leave loses nothing).

    Consumption order is deterministic: :meth:`next_item` round-robins
    the admitted workers in wid order, so a seeded chaos run replays
    the identical item sequence (the pool analogue of the FaultPlan
    event witness).  Admission itself runs one thread per incoming
    connection (a silent stray parked in its handshake cannot delay a
    healthy joiner), so workers that connect CONCURRENTLY race for wid
    order — a caller that needs a reproducible order across runs
    (seeded replay) serializes joins via :meth:`wait_for_workers`, as
    the chaos tests do.
    """

    def __init__(self, port: int, host: str = "localhost",
                 heartbeat_timeout: float = 0.0,
                 rejoin_budget: int = 4,
                 recv_deadline: float = 0.0,
                 accept_timeout: float = 0.5,
                 staleness: Optional[int] = None,
                 tracer=None):
        self.host = host
        #: Learner-side tracer for every member channel (None = the
        #: process tracer); membership events mirror into it.
        self._tracer = tracer
        self.heartbeat_timeout = heartbeat_timeout
        self.rejoin_budget = rejoin_budget
        self.recv_deadline = recv_deadline
        #: The learner's staleness bound; rides every HELLO ack so the
        #: worker-side capacity gate enforces the LEARNER's configured
        #: bound, not a per-process default.  PoolOrchestrator sets it
        #: from cfg.async_staleness.
        self.staleness = staleness
        self.watchdog = Watchdog()
        self._lock = threading.Lock()
        self._members: Dict[int, PoolMember] = {}
        self._order: List[int] = []      # admission order (rr rotation)
        self._rr = 0
        self._next_wid = 0
        self._rejoins = 0                # admissions after a departure
        self._stop = threading.Event()
        self._weights: Optional[Tuple[int, Any]] = None  # latest bcast
        self.events: List[Tuple[str, Any]] = []
        self.recovery = {"worker_joins": 0, "worker_deaths": 0,
                         "worker_leaves": 0, "discarded_batches": 0,
                         "worker_refused": 0}

        self._srv = socket.socket()
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(16)
        self._srv.settimeout(accept_timeout)
        self.port = self._srv.getsockname()[1]
        # The accept loop itself runs under the same watchdog as the
        # workers it admits (liveness record only — it blocks in
        # accept() by design, so no stall timeout).
        accept_hb = self.watchdog.register("pool-accept", timeout=0.0)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, args=(accept_hb,),
            name="pool-accept", daemon=True)
        self._accept_thread.start()

    @classmethod
    def from_config(cls, rcfg, port: int = 0,
                    host: str = "localhost", tracer=None) -> "WorkerPool":
        """Construct the learner-side pool from
        ``TrainConfig.resilience`` — the knobs documented there
        (`heartbeat_timeout`, `rejoin_budget`,
        `channel_recv_deadline`) actually drive the pool through
        here."""
        return cls(port, host=host,
                   heartbeat_timeout=rcfg.heartbeat_timeout,
                   rejoin_budget=rcfg.rejoin_budget,
                   recv_deadline=rcfg.channel_recv_deadline,
                   tracer=tracer)

    # -- membership ----------------------------------------------------
    def _trc(self):
        return self._tracer if self._tracer is not None else \
            obs.get_tracer()

    def _event(self, kind: str, detail) -> None:
        with self._lock:
            self.events.append((kind, detail))
        tr = self._trc()
        if tr.enabled:
            tr.instant("pool." + kind, detail=repr(detail))

    def live_members(self) -> List[PoolMember]:
        with self._lock:
            return [m for m in self._members.values() if m.alive]

    def consumable_members(self) -> List[PoolMember]:
        """Members the learner can still draw from: alive, or departed
        with a non-empty backlog (graceful leavers only — a crashed
        member's queue was already discarded)."""
        with self._lock:
            return [m for m in self._members.values()
                    if m.alive or not m.queue.empty()]

    def wait_for_workers(self, n: int, timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        while len(self.live_members()) < n:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"worker pool: only {len(self.live_members())}/{n} "
                    f"workers joined within {timeout:.1f}s")
            time.sleep(0.02)

    def _accept_loop(self, hb) -> None:
        while not self._stop.is_set():
            hb.beat()
            try:
                conn, addr = self._srv.accept()
            except socket.timeout:
                continue
            except OSError as e:
                if self._stop.is_set():
                    return  # server socket closed by shutdown()
                # Transient accept failure (ECONNABORTED from a peer
                # that RST before we got here, EMFILE under fd
                # pressure): the accept loop IS the pool's elastic
                # membership — one flaky connection must not end all
                # future admissions.
                _LOG.warning("worker pool accept error (transient, "
                             "loop continues): %r", e)
                time.sleep(0.1)
                continue
            # Admission runs in a short-lived per-connection thread:
            # _admit blocks on the peer's HELLO (deadlined, floor
            # 10 s), and a silent stray peer parked in that handshake
            # must not serialize behind it a healthy worker joining
            # right after — an empty pool only waits `rejoin_grace`
            # (default 2 s) before firing the degradation ladder, so
            # inline admission could degrade the learner with a
            # healthy worker sitting in the accept backlog.
            threading.Thread(  # orion: ignore[unsupervised-thread] handshake thread is strictly deadlined (recv deadline >= 10s + SO_SNDTIMEO), not a long-lived worker
                target=self._admit_conn, args=(conn, addr),
                name=f"pool-admit-{addr[1] if len(addr) > 1 else addr}",
                daemon=True).start()

    def _admit_conn(self, conn: socket.socket, addr) -> None:
        try:
            self._admit(conn, addr)
        except (ProtocolError, ConnectionError, TimeoutError,
                pickle.UnpicklingError) as e:
            # A stray/mismatched peer fails ITS admission with a
            # clear error; the pool (and its live workers) sail on.
            # Counter increments take the pool lock: admission threads,
            # recv threads and the learner all bump ``recovery``, and a
            # dict-entry += is a read-modify-write that drops updates
            # under contention (lock-discipline rule).
            with self._lock:
                self.recovery["worker_refused"] += 1
            self._event("worker-refused", repr(e))
            _LOG.warning("worker pool refused a peer at %s: %s",
                         addr, e)
            try:
                conn.close()
            except OSError:
                pass

    def _admit(self, conn: socket.socket, addr) -> None:
        chan = PyTreeChannel(conn, recv_deadline=max(
            self.recv_deadline, 10.0) if self.recv_deadline else 10.0,
            tracer=self._tracer)
        # The handshake itself is deadlined: a peer that connects and
        # goes silent must not wedge the accept loop.
        kind, hello = chan.recv_frame()
        if kind != FRAME_HELLO:
            raise ProtocolError(
                f"expected HELLO, got {_FRAME_NAMES.get(kind, kind)}")
        # The rejoin budget bounds CHURN, not pool size: admissions
        # while no member has ever died or left are the initial pool
        # (any count); every admission after the first death/leave is
        # a rejoin, and a worker flapping in a crash loop must not
        # grind the learner through more than ``rejoin_budget``
        # re-syncs.  Check-and-reserve in ONE lock acquisition:
        # admission threads run concurrently, and two simultaneous
        # rejoins must not both pass a budget of one.
        with self._lock:
            ever_departed = (self.recovery["worker_deaths"]
                             + self.recovery["worker_leaves"]) > 0
            exhausted = (ever_departed
                         and self._rejoins >= self.rejoin_budget)
            reserved = ever_departed and not exhausted
            if reserved:
                self._rejoins += 1
        if exhausted:
            # Counters first: the GOODBYE frame races the caller's
            # "was it refused?" check the moment it hits the wire.
            with self._lock:
                self.recovery["worker_refused"] += 1
            self._event("worker-refused",
                        f"rejoin budget ({self.rejoin_budget})")
            chan.send_frame(FRAME_GOODBYE,
                            {"reason": "rejoin budget exhausted"})
            chan.close()
            return
        try:
            self._admit_reserved(chan, hello)
        except BaseException:
            # A connection dropping mid-handshake refunds its slot:
            # four transient handshake drops must not exhaust the
            # budget and lock out genuinely healthy rejoiners.
            if reserved:
                with self._lock:
                    self._rejoins -= 1
            raise

    def _admit_reserved(self, chan: PyTreeChannel, hello: dict) -> None:
        """Post-budget half of admission: ack, register, start the
        recv thread.  Raising out of here refunds the caller's
        rejoin-budget reservation."""
        # Restore the caller's recv deadline after the handshake.
        chan.set_recv_deadline(self.recv_deadline)
        with self._lock:
            wid = self._next_wid
            self._next_wid += 1
            weights = self._weights
        name = str(hello.get("name", f"worker-{wid}"))
        ack = {"wid": wid, "protocol": PROTOCOL_VERSION}
        if self.staleness is not None:
            ack["staleness"] = int(self.staleness)
        if weights is not None:
            ack["version"], ack["params"] = weights
        # The ack send is the last step that can fail: nothing is
        # registered yet, so a connection dropping mid-handshake
        # leaks no watchdog heartbeat.
        chan.send_frame(FRAME_HELLO, ack)
        hb = self.watchdog.register(
            f"pool-worker-{wid}", timeout=self.heartbeat_timeout)
        member = PoolMember(wid, name, chan, hb)
        if weights is not None:
            member.version = weights[0]
        member.thread = threading.Thread(
            target=self._recv_loop, args=(member,),
            name=f"pool-recv-{wid}", daemon=True)
        with self._lock:
            admitted = not self._stop.is_set()
            if admitted:
                self._members[wid] = member
                self._order.append(wid)
        if not admitted:
            # shutdown() raced the handshake (admission threads can
            # straddle it): release the peer instead of registering a
            # member nobody will ever close.  (ConnectionError, not
            # return: the caller's refund path must see a failure.)
            self.watchdog.unregister(member.hb.name)
            try:
                chan.send_frame(FRAME_GOODBYE, {"reason": "shutdown"})
            except (ConnectionError, TimeoutError, OSError):
                pass
            try:
                chan.close()
            except OSError:
                pass
            raise ConnectionError("pool shut down during admission")
        member.thread.start()
        with self._lock:
            self.recovery["worker_joins"] += 1
        self._event("worker-join", (wid, name))
        _LOG.info("worker pool admitted %s as wid=%d (%d live)",
                  name, wid, len(self.live_members()))

    def _recv_loop(self, member: PoolMember) -> None:
        """One thread per worker: demultiplex its frames.  EOF or any
        channel error ⇒ crash (unless a GOODBYE already arrived)."""
        try:
            while not self._stop.is_set():
                kind, payload = member.chan.recv_frame()
                if kind == FRAME_HEARTBEAT:
                    member.hb.beat()
                elif kind == FRAME_TRAJ:
                    member.hb.beat()  # a trajectory is the best heartbeat
                    if self._trc().enabled:
                        # The worker's generate-span id (same thread
                        # just parsed this frame's header): the
                        # learner's consume event links to it.
                        payload["_obs_parent"] = \
                            member.chan.last_remote_ctx[1]
                    # Gated under the pool lock against _mark_dead: a
                    # frame landing after another thread declared this
                    # worker dead (e.g. a failed broadcast send) must
                    # be discarded too, or it would sit in a dead
                    # member's queue looking like a leaver's backlog.
                    with self._lock:
                        if member.alive:
                            member.produced += 1
                            member.queue.put(payload)
                        else:
                            self.recovery["discarded_batches"] += 1
                elif kind == FRAME_WEIGHTS_ACK:
                    # v7 push handshake: the worker confirms a weight
                    # version landed — ``staged`` (held inactive until
                    # commit) or applied.  The commit point in
                    # :meth:`push_weights` gates on these.
                    member.hb.beat()
                    with self._lock:
                        v = int(payload["version"])
                        if payload.get("staged"):
                            member.staged_version = max(
                                member.staged_version, v)
                        else:
                            member.acked_version = max(
                                member.acked_version, v)
                elif kind == FRAME_GOODBYE:
                    self._mark_left(member)
                    return
                else:
                    raise ProtocolError(
                        f"unexpected {_FRAME_NAMES.get(kind, kind)} "
                        "frame from worker")
        except (ConnectionError, TimeoutError, OSError, EOFError,
                pickle.UnpicklingError) as e:
            if not member.left and not self._stop.is_set():
                self._mark_dead(member, repr(e))

    def _mark_left(self, member: PoolMember) -> None:
        with self._lock:
            if member.left or not member.alive:
                return
            member.left = True
            member.alive = False
            self.recovery["worker_leaves"] += 1
        self.watchdog.unregister(member.hb.name)
        self._event("worker-leave", member.wid)
        _LOG.info("worker wid=%d said GOODBYE (graceful; %d queued "
                  "batches stay consumable)", member.wid,
                  member.queue.qsize())
        # The backlog lives in the queue, not the socket: close the
        # channel now (its recv thread has returned) or every leaver
        # in a long churn-heavy run parks an fd in CLOSE_WAIT until
        # pool shutdown.
        try:
            member.chan.close()
        except OSError:
            pass

    def _mark_dead(self, member: PoolMember, reason: str) -> None:
        with self._lock:
            if not member.alive:
                return
            member.alive = False
        self.watchdog.unregister(member.hb.name)
        # Discard the in-flight backlog: a crashed worker's queued
        # trajectories are suspect (torn send, stale params, the very
        # batch that killed it) and are NEVER donated to the optimizer.
        discarded = 0
        while True:
            try:
                member.queue.get_nowait()
                discarded += 1
            except queue.Empty:
                break
        with self._lock:
            self.recovery["worker_deaths"] += 1
            self.recovery["discarded_batches"] += discarded
            # Snapshot for the flight dump below while we hold the
            # lock — another recv/admission thread may be mid-update.
            recovery_snap = dict(self.recovery)
        self._event("worker-death", (member.wid, discarded))
        _LOG.error("worker wid=%d dead (%s); %d in-flight batches "
                   "discarded; %d workers remain", member.wid, reason,
                   discarded, len(self.live_members()))
        # Forensics: the moment the ladder's first rung fires is
        # exactly when the recent timeline matters — dump it (no-op
        # without an installed recorder, never raises).
        obs.flight_dump("worker-death", {
            "transition": "degradation-ladder: worker marked dead, "
                          "survivors absorb the load",
            "wid": member.wid, "name": member.name, "reason": reason,
            "discarded": discarded, "recovery": recovery_snap})
        try:
            member.chan.close()
        except OSError:
            pass

    def reap_stalled(self) -> List[int]:
        """Supervisor poll: mark every worker whose heartbeat is past
        ``heartbeat_timeout`` dead.  Returns the reaped wids."""
        reaped = []
        stalled = set(self.watchdog.stalled())
        with self._lock:
            candidates = [m for m in self._members.values()
                          if m.alive and m.hb.name in stalled]
        for m in candidates:
            self._mark_dead(m, f"missed heartbeats "
                               f"({self.heartbeat_timeout:.1f}s)")
            reaped.append(m.wid)
        return reaped

    def retire_member(self, wid: Optional[int] = None) -> Optional[int]:
        """Graceful scale-down: send GOODBYE to one live member (the
        NEWEST joiner when ``wid`` is None — last in, first out, so the
        longest-warmed member keeps serving) and return its wid.  The
        worker's recv loop sees the GOODBYE, finishes its in-flight
        batch, and leaves via the normal graceful path — its queued
        trajectories stay consumable, unlike a kill.  Returns None when
        no live member exists; a member whose channel is already broken
        is marked dead instead (the retire still "succeeded" in the
        sense that the pool shrank)."""
        with self._lock:
            live = [m for m in self._members.values() if m.alive]
            if wid is not None:
                live = [m for m in live if m.wid == wid]
            if not live:
                return None
            member = max(live, key=lambda m: m.wid)
        try:
            member.chan.send_frame(FRAME_GOODBYE,
                                   {"reason": "scale-down"})
        except (ConnectionError, TimeoutError, OSError) as e:
            self._mark_dead(member, f"retire send failed: {e!r}")
            return member.wid
        self._event("worker-retire", member.wid)
        return member.wid

    # -- weight fan-out -------------------------------------------------
    def broadcast(self, params_host: Any, version: int) -> int:
        """Fan a WEIGHTS frame out to every live worker; returns how
        many received it.  A send that fails marks that worker dead —
        the broadcast never takes the pool down.  The snapshot is
        pickled ONCE and the shared bytes fanned out (per-worker
        flow-control state rides the tiny ACK frames instead — see
        :meth:`next_item` — precisely so this payload stays identical
        across workers)."""
        with self._lock:
            self._weights = (version, params_host)
            members = [self._members[w] for w in self._order
                       if self._members[w].alive]
        blob = pickle.dumps({"version": version, "params": params_host},
                            protocol=pickle.HIGHEST_PROTOCOL)
        sent = 0
        for m in members:
            try:
                m.chan.send_raw(FRAME_WEIGHTS, blob)
                m.version = version
                sent += 1
            except (ConnectionError, TimeoutError, OSError) as e:
                self._mark_dead(m, f"weight broadcast failed: {e!r}")
        return sent

    def broadcast_version(self, version: int) -> int:
        """Version-tag-only fan-out for iterations that changed NO
        byte of the params (a quarantined update): workers stamp
        future TRAJ frames with the advanced version so the staleness
        metrics stay aligned, without re-shipping a multi-GB
        byte-identical snapshot.  The client keeps its current params
        (a WEIGHTS frame with no ``params`` key)."""
        with self._lock:
            if self._weights is not None:
                self._weights = (version, self._weights[1])
            members = [self._members[w] for w in self._order
                       if self._members[w].alive]
        sent = 0
        for m in members:
            try:
                m.chan.send_frame(FRAME_WEIGHTS, {"version": version})
                m.version = version
                sent += 1
            except (ConnectionError, TimeoutError, OSError) as e:
                self._mark_dead(m, f"version broadcast failed: {e!r}")
        return sent

    def _send_weights_ctl(self, key: str, version: int) -> int:
        """Fan a tiny WEIGHTS control frame (``{key: version}`` —
        ``commit`` or ``abort``) out to every live member; a failed
        send marks that worker dead, same as :meth:`broadcast`."""
        with self._lock:
            members = [self._members[w] for w in self._order
                       if self._members[w].alive]
        sent = 0
        for m in members:
            try:
                m.chan.send_frame(FRAME_WEIGHTS, {key: int(version)})
                if key == "commit":
                    m.version = int(version)
                sent += 1
            except (ConnectionError, TimeoutError, OSError) as e:
                self._mark_dead(m, f"weights {key} send failed: {e!r}")
        return sent

    def broadcast_staged(self, params_host: Any, version: int) -> int:
        """Phase one of the v7 two-phase push: ship the snapshot with
        ``staged=True`` — workers hold it INACTIVE (generation keeps
        running on the old params) and WEIGHTS_ACK it as staged.  The
        snapshot only becomes live when :meth:`_send_weights_ctl`
        ships the commit; a learner that dies in between leaves every
        worker on the old version (a torn push self-heals)."""
        with self._lock:
            members = [self._members[w] for w in self._order
                       if self._members[w].alive]
        blob = pickle.dumps({"version": version, "params": params_host,
                             "staged": True},
                            protocol=pickle.HIGHEST_PROTOCOL)
        sent = 0
        for m in members:
            try:
                m.chan.send_raw(FRAME_WEIGHTS, blob)
                sent += 1
            except (ConnectionError, TimeoutError, OSError) as e:
                self._mark_dead(m, f"staged broadcast failed: {e!r}")
        return sent

    def wait_weights_ack(self, version: int, timeout: float = 30.0,
                         staged: bool = False) -> bool:
        """Block until every LIVE member has WEIGHTS_ACK'd ``version``
        (as staged when ``staged=True``, else as applied).  Members
        that die while we wait stop being waited on — the commit point
        gates on the survivors, and the push layer decides whether a
        shrunken fleet is acceptable.  Returns False on timeout."""
        deadline = time.monotonic() + timeout
        attr = "staged_version" if staged else "acked_version"
        while True:
            with self._lock:
                lagging = [m.wid for m in self._members.values()
                           if m.alive and getattr(m, attr) < version]
            if not lagging:
                return True
            if time.monotonic() >= deadline:
                _LOG.warning(
                    "weights v%d %s-ack timed out; lagging wids=%s",
                    version, "staged" if staged else "applied", lagging)
                return False
            time.sleep(0.01)

    def push_weights(self, params_host: Any, version: int,
                     timeout: float = 30.0) -> bool:
        """The production model-push path (v7): stage the snapshot on
        every live worker, wait for all staged ACKs, then commit —
        workers swap atomically and ACK the applied version.  Any
        failure before the commit point aborts the push: workers drop
        the staged snapshot and keep generating on the OLD version
        (``weights.push`` is the chaos boundary).  Returns True only
        when every live member applied the new version."""
        fault_point("weights.push")
        obs.instant("pool.push-weights", version=version)
        try:
            if self.broadcast_staged(params_host, version) == 0:
                return False
            if not self.wait_weights_ack(version, timeout=timeout,
                                         staged=True):
                self._send_weights_ctl("abort", version)
                return False
        except Exception:
            self._send_weights_ctl("abort", version)
            raise
        # Commit point: every live worker holds the staged snapshot.
        with self._lock:
            self._weights = (version, params_host)
        self._send_weights_ctl("commit", version)
        ok = self.wait_weights_ack(version, timeout=timeout)
        self._event("weights-push", (version, ok))
        return ok

    # -- deterministic consumption ---------------------------------------
    def next_item(self, timeout: float = 0.1
                  ) -> Optional[Tuple[PoolMember, Any]]:
        """Backlog-first round-robin dequeue in admission order.

        Whose turn: the first rotation member (starting at ``_rr``)
        with a READY batch; when every queue keeps pace this is strict
        round-robin, and an alive worker with an empty queue never
        blocks another worker's ready batch (no head-of-line
        starvation by a slow or wedged-but-heartbeating member).  With
        nothing ready, blocks briefly on the rotation's first alive
        member.  Returns None when nothing is consumable within the
        timeout (caller decides whether the pool is empty —
        :meth:`consumable_members`)."""
        deadline = time.monotonic() + timeout
        while True:
            with self._lock:
                order = list(self._order)
                members = dict(self._members)
            if not order:
                if time.monotonic() >= deadline:
                    return None
                time.sleep(min(0.02, timeout))
                continue
            chosen = None
            fallback = None     # first ALIVE member: wait on its queue
            for off in range(len(order)):
                m = members[order[(self._rr + off) % len(order)]]
                if not m.queue.empty():
                    chosen = m
                    self._rr = (self._rr + off) % len(order)
                    break
                if fallback is None and m.alive:
                    fallback = m
                    fb_off = off
            if chosen is None:
                if fallback is None:
                    return None  # pool is empty (the ladder's trigger)
                chosen = fallback
                self._rr = (self._rr + fb_off) % len(order)
            try:
                item = chosen.queue.get(timeout=0.05)
            except queue.Empty:
                # Its queue stayed empty: if it died (or left) while we
                # waited, rotate past it on the next spin.
                if time.monotonic() >= deadline:
                    return None
                continue
            with self._lock:
                suspect = not chosen.alive and not chosen.left
                if suspect:
                    # get() raced _mark_dead's queue drain and stole
                    # an item the drain was about to throw away.  A
                    # crashed worker's batch is suspect no matter
                    # which thread pulled it off the queue — discard
                    # it here (the drain can no longer see it, so it
                    # counts it nowhere).
                    self.recovery["discarded_batches"] += 1
            if suspect:
                self._event("discard-raced", chosen.wid)
                continue
            chosen.consumed += 1
            self._rr = (self._rr + 1) % max(len(order), 1)
            if chosen.alive:
                # Per-worker backpressure: the consumed count goes
                # back as a tiny ACK frame — the client-side
                # capacity gate (`PoolWorkerClient._wait_capacity`)
                # bounds that worker's in-flight batches on it.
                # (A leaver's backlog needs no ACK: nobody is
                # gating on it.)
                try:
                    chosen.chan.send_frame(
                        FRAME_ACK, {"consumed": chosen.consumed})
                except (ConnectionError, TimeoutError, OSError) as e:
                    self._mark_dead(
                        chosen, f"consume-ack send failed: {e!r}")
                    # The peer was already dead when we pulled this
                    # item — same invariant as the suspect re-check
                    # above: a crashed worker's batch is discarded,
                    # never donated.
                    with self._lock:
                        self.recovery["discarded_batches"] += 1
                    self._event("discard-raced", chosen.wid)
                    continue
            return chosen, item

    # -- shutdown --------------------------------------------------------
    def shutdown(self, goodbye: bool = True) -> None:
        """Stop admitting, optionally GOODBYE every live worker (the
        preemption path — workers distinguish this from a crash), and
        close every channel."""
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            members = list(self._members.values())
        for m in members:
            if goodbye and m.alive:
                try:
                    m.chan.send_frame(FRAME_GOODBYE, {"reason": "shutdown"})
                except (ConnectionError, TimeoutError, OSError):
                    pass
            try:
                m.chan.close()
            except OSError:
                pass
            self.watchdog.unregister(m.hb.name)
        if self._accept_thread.is_alive():
            self._accept_thread.join(timeout=2.0)

    close = shutdown


# ---------------------------------------------------------------------------
# worker side: the pool client
# ---------------------------------------------------------------------------


class PoolWorkerClient:
    """Rollout-process side of the pool protocol.

    Connects, HELLOs (``worker.hello`` fault point), then runs two
    supervised daemon threads: a heartbeat sender
    (``worker.heartbeat``) and a receiver that keeps the latest
    WEIGHTS snapshot (latest-wins) and watches for the learner's
    GOODBYE.  :meth:`run` is the generation loop; the caller supplies
    only ``generate_fn`` — everything protocol-shaped (staleness gate,
    version tags, fault points, GOODBYE-on-exit, crash-on-error
    semantics) lives here so every worker process behaves identically.
    """

    def __init__(self, port: int, host: str = "localhost",
                 name: Optional[str] = None,
                 heartbeat_interval: float = 0.5,
                 connect_timeout: float = 120.0,
                 seed: Optional[int] = None,
                 recv_deadline: float = 0.0,
                 tracer=None):
        self.name = name or f"worker-{os.getpid()}"
        self.heartbeat_interval = heartbeat_interval
        self._tracer = tracer
        self.watchdog = Watchdog()
        self._lock = threading.Lock()
        self._weights_cv = threading.Condition(self._lock)
        self._version = -1
        self._params: Any = None
        #: v7 two-phase push: (version, params) held inactive until the
        #: learner's commit frame promotes it (abort drops it).
        self._staged: Optional[Tuple[int, Any]] = None
        self.goodbye = threading.Event()   # learner asked us to leave
        self.closed = threading.Event()    # channel is gone
        self._sent = 0
        self._acked = 0   # learner-consumed count (rides ACK frames)
        fault_point("worker.hello")
        self.chan = PyTreeChannel.connect(
            port, host=host, timeout=connect_timeout, seed=seed,
            recv_deadline=recv_deadline, tracer=tracer)
        self.chan.send_frame(FRAME_HELLO,
                             {"name": self.name, "pid": os.getpid(),
                              "protocol": PROTOCOL_VERSION})
        kind, ack = self.chan.recv_frame()
        if kind == FRAME_GOODBYE:
            self.chan.close()
            raise ConnectionError(
                f"worker pool refused {self.name}: "
                f"{ack.get('reason', 'no reason given')}")
        if kind != FRAME_HELLO:
            self.chan.close()
            raise ProtocolError(
                f"expected HELLO ack, got {_FRAME_NAMES.get(kind, kind)}")
        self.wid = int(ack["wid"])
        #: The LEARNER's configured staleness bound (cfg.async_staleness
        #: via PoolOrchestrator → WorkerPool.staleness → this ack);
        #: :meth:`run` defaults to it so every worker process honors
        #: the learner's bound without local plumbing.
        self.learner_staleness = (int(ack["staleness"])
                                  if "staleness" in ack else None)
        if "params" in ack:
            self._version = int(ack["version"])
            self._params = ack["params"]
        # Distributed tracing: the HELLO ack's header carries the
        # LEARNER's trace id — adopt it so every span this worker
        # records stitches into the learner's trace (one trace id
        # across the whole pool).
        self._trc().adopt_trace(self.chan.last_remote_ctx[0])
        # Both client threads run under the client's own watchdog —
        # the run loop is their supervisor (lint: unsupervised-thread).
        hb_beat = self.watchdog.register(f"hb-send-{self.wid}", timeout=0.0)
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, args=(hb_beat,),
            name="pool-heartbeat", daemon=True)
        self._hb_thread.start()
        rx_beat = self.watchdog.register(f"rx-{self.wid}", timeout=0.0)
        self._rx_thread = threading.Thread(
            target=self._recv_loop, args=(rx_beat,),
            name="pool-client-recv", daemon=True)
        self._rx_thread.start()

    @classmethod
    def from_config(cls, rcfg, port: int, host: str = "localhost",
                    name: Optional[str] = None,
                    seed: Optional[int] = None,
                    tracer=None) -> "PoolWorkerClient":
        """Construct the worker-side client from
        ``TrainConfig.resilience`` (`heartbeat_interval`,
        `channel_recv_deadline`) — every worker process of a job
        built from the same config speaks the same cadence.
        ``tracer`` (tests standing in for processes) defaults to the
        process tracer."""
        return cls(port, host=host, name=name,
                   heartbeat_interval=rcfg.heartbeat_interval,
                   recv_deadline=rcfg.channel_recv_deadline,
                   seed=seed, tracer=tracer)

    def _trc(self):
        return self._tracer if self._tracer is not None else \
            obs.get_tracer()

    # -- background threads ---------------------------------------------
    def _heartbeat_loop(self, beat) -> None:
        while not self.closed.is_set() and not self.goodbye.is_set():
            beat.beat()
            try:
                fault_point("worker.heartbeat")
                self.chan.send_frame(FRAME_HEARTBEAT,
                                     {"t": time.monotonic()})
            except (ConnectionError, TimeoutError, OSError) as e:
                _LOG.warning("worker %s heartbeat send failed: %r",
                             self.name, e)
                self.closed.set()
                return
            except Exception:
                # An injected heartbeat fault: skip this beat (the
                # learner sees a MISSED heartbeat, which is the
                # scenario under test), keep the sender alive.
                pass
            self.closed.wait(self.heartbeat_interval)

    def _recv_loop(self, beat) -> None:
        try:
            while not self.closed.is_set():
                beat.beat()
                kind, payload = self.chan.recv_frame()
                if kind == FRAME_WEIGHTS:
                    # Keep the trace id fresh: a worker admitted
                    # before the learner enabled tracing adopts on
                    # the first traced WEIGHTS frame instead.
                    self._trc().adopt_trace(self.chan.last_remote_ctx[0])
                    ack = None
                    with self._weights_cv:
                        if "commit" in payload:
                            # v7 commit: promote the staged snapshot.
                            # A commit for a version we never staged
                            # (joined mid-push) is ignored — the
                            # learner's next full broadcast catches us
                            # up; committing nothing would be worse.
                            v = int(payload["commit"])
                            if self._staged is not None and \
                                    self._staged[0] == v:
                                self._version, self._params = self._staged
                                self._staged = None
                                ack = {"version": v}
                        elif "abort" in payload:
                            # Torn push: drop the staged snapshot, keep
                            # generating on the old params.
                            v = int(payload["abort"])
                            if self._staged is not None and \
                                    self._staged[0] == v:
                                self._staged = None
                        elif payload.get("staged"):
                            # Phase one: hold the snapshot INACTIVE
                            # until the learner's commit — old params
                            # stay live across the whole fleet until
                            # the commit point.
                            v = int(payload["version"])
                            self._staged = (v, payload.get("params"))
                            ack = {"version": v, "staged": True}
                        else:
                            # Latest-wins: a slow worker skips straight
                            # to the freshest snapshot instead of
                            # replaying every intermediate version.  A
                            # version-only frame (no params key: a
                            # quarantined update changed nothing)
                            # advances the tag and keeps the current
                            # snapshot.
                            self._version = int(payload["version"])
                            if "params" in payload:
                                self._params = payload["params"]
                            ack = {"version": self._version}
                        self._weights_cv.notify_all()
                    if ack is not None:
                        self.chan.send_frame(FRAME_WEIGHTS_ACK, ack)
                elif kind == FRAME_ACK:
                    with self._weights_cv:
                        self._acked = max(self._acked,
                                          int(payload["consumed"]))
                        self._weights_cv.notify_all()
                elif kind == FRAME_GOODBYE:
                    self.goodbye.set()
                    with self._weights_cv:
                        self._weights_cv.notify_all()
                    return
                else:
                    # The learner only ever sends WEIGHTS/ACK/GOODBYE
                    # after the handshake: anything else is protocol
                    # confusion, and silently dropping it would leave
                    # a skewed peer undetected until it wedged the
                    # staleness gate.  ProtocolError is a
                    # ConnectionError — the except below sets
                    # ``closed`` and wakes every waiter, same as any
                    # other broken channel (frame-exhaustive rule).
                    raise ProtocolError(
                        f"unexpected {_FRAME_NAMES.get(kind, kind)} "
                        "frame from learner")
        except (ConnectionError, TimeoutError, OSError, EOFError,
                pickle.UnpicklingError):
            self.closed.set()
            with self._weights_cv:
                self._weights_cv.notify_all()

    # -- weights ---------------------------------------------------------
    def weights(self) -> Tuple[int, Any]:
        with self._lock:
            return self._version, self._params

    def wait_weights(self, min_version: int,
                     timeout: float = 120.0) -> Tuple[int, Any]:
        """Block until a snapshot with version ≥ ``min_version`` has
        arrived (the worker-side staleness gate)."""
        deadline = time.monotonic() + timeout
        with self._weights_cv:
            while self._version < min_version:
                if self.goodbye.is_set() or self.closed.is_set():
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"worker {self.name}: no weights ≥ "
                        f"v{min_version} within {timeout:.1f}s "
                        f"(have v{self._version})")
                self._weights_cv.wait(timeout=min(remaining, 0.1))
            return self._version, self._params

    def _wait_capacity(self, max_ahead: int) -> None:
        """Block while more than ``max_ahead`` of OUR batches sit
        unconsumed at the learner — the per-worker staleness gate.

        The pool's global version counter cannot carry this bound: it
        advances once per consumed item across ALL workers, so gating
        on it (the 1×1 split's trick) lets a fast worker in an
        N-worker pool free-run arbitrarily ahead — unbounded
        learner-side queue, staleness metrics far past the configured
        bound.  The learner's per-worker consumed count arrives on ACK
        frames instead (see :meth:`WorkerPool.next_item`).

        Deliberately NO deadline: a learner that pauses consuming (a
        long compile, an eval, a gap between train() calls) is not a
        failure, and timing out here would convert it into silent
        worker churn.  Liveness is the receive thread's job — a dead
        learner errors it out, which sets ``closed`` and wakes this
        wait, as do GOODBYE and SO_KEEPALIVE-detected host death."""
        with self._weights_cv:
            while self._sent - self._acked > max_ahead:
                if self.goodbye.is_set() or self.closed.is_set():
                    return
                self._weights_cv.wait(timeout=0.1)

    # -- trajectory sends ------------------------------------------------
    def send_traj(self, payload: dict, version: int) -> None:
        fault_point("worker.traj")
        self.chan.send_frame(FRAME_TRAJ,
                             {"worker": self.wid, "seq": self._sent,
                              "version": version, "item": payload})
        self._sent += 1

    # -- lifecycle -------------------------------------------------------
    def leave(self, reason: str = "done") -> None:
        """Graceful exit: GOODBYE then close — the learner keeps our
        queued batches and records a leave, not a death.  The path the
        preemption handler takes on SIGTERM."""
        if not self.closed.is_set():
            try:
                self.chan.send_frame(FRAME_GOODBYE, {"reason": reason})
            except (ConnectionError, TimeoutError, OSError):
                pass
        self.close()

    def close(self) -> None:
        self.closed.set()
        with self._weights_cv:
            self._weights_cv.notify_all()
        try:
            self.chan.close()
        except OSError:
            pass

    def run(self, generate_fn: Callable[[int, int, Any], dict],
            n_batches: Optional[int] = None,
            staleness: Optional[int] = None,
            preemption=None) -> int:
        """The worker generation loop.  ``generate_fn(i, version,
        params_host)`` returns the TRAJ payload for batch ``i`` (result
        fields + scores, numpy).  Returns batches sent.

        ``staleness`` defaults to the LEARNER's configured bound from
        the HELLO ack (``learner_staleness``), so the value set once
        on ``cfg.async_staleness`` governs every worker process; pass
        it explicitly only to override for a test.

        Semantics: a learner GOODBYE (or ``preemption`` requested)
        exits gracefully with our own GOODBYE; ``generate_fn`` raising
        is a CRASH — the socket drops with no GOODBYE, which is
        exactly the signal the learner's supervisor keys on."""
        if staleness is None:
            staleness = (self.learner_staleness
                         if self.learner_staleness is not None else 1)
        i = 0
        in_gen = False
        try:
            while n_batches is None or i < n_batches:
                if self.goodbye.is_set() or self.closed.is_set():
                    break
                if preemption is not None and preemption.requested:
                    break
                # Staleness gate (worker side): never run more than
                # ``staleness`` batches ahead of what the learner has
                # consumed FROM US (per-worker backpressure —
                # `_wait_capacity` explains why the global version
                # counter cannot carry this bound), then generate with
                # the newest weights received (latest-wins).
                self._wait_capacity(staleness)
                if self.goodbye.is_set() or self.closed.is_set():
                    break
                version, params = self.wait_weights(0)
                if self.goodbye.is_set() or self.closed.is_set():
                    break
                # The span covers generate AND the TRAJ send, so the
                # frame header carries this span's id — the learner's
                # consume event names it as its parent (cross-process
                # causality).  No-op when tracing is off.
                with self._trc().span("rollout.generate", batch=i,
                                      version=version, wid=self.wid):
                    in_gen = True
                    payload = generate_fn(i, version, params)
                    in_gen = False
                    self.send_traj(payload, version)
                i += 1
        except (ConnectionError, TimeoutError, OSError):
            self.close()
            if in_gen:
                # generate_fn is CALLER code (reward scoring, data
                # loading): its ConnectionError / FileNotFoundError is
                # a worker CRASH the process supervisor must see, not
                # a quiet "learner gone" exit 0.
                raise
            return i  # learner gone: nothing left to crash loudly at
        except BaseException:
            # Crash semantics: die with the socket open-then-dropped,
            # NO goodbye — the learner must see a death, not a leave.
            self.close()
            raise
        self.leave("preempted" if (preemption is not None
                                   and preemption.requested)
                   else "complete")
        return i
