"""Token-streaming, multi-tenant serving gateway (ISSUE 12 tentpole).

The continuous engine became a standing service in PR 8 and learned
token-level streaming + per-tenant QoS in this PR — but its only
client lived in-process.  This module is the network front door: a
:class:`ServingGateway` accepts remote clients over the hardened
``ORTP`` framed channel (magic + version header, keepalive, recv
deadlines — the exact transport the worker pool runs on) and fans
completion tokens out AS THE ENGINE HARVESTS THEM, so a remote
client's observed TTFT is first-token time, not full-completion time.

Second frame family on the channel (protocol v5):

- ``FRAME_SUBMIT``  client → gateway: prompt ids + budget / priority /
  deadline under the client's connection-bound tenant;
- ``FRAME_STREAM``  gateway → client: incremental token chunks
  (``done`` marks the final chunk, which carries the full completion
  incl. logprobs), stream restarts after preemption, and typed error
  payloads — an :class:`~orion_tpu.rollout.continuous.EngineOverloaded`
  shed is forwarded with its queue depth + retry-after hint and
  re-raised as the same typed error client-side;
- ``FRAME_CANCEL``  client → gateway: abort an in-flight request.

HELLO / GOODBYE are shared with the pool protocol: a client's HELLO
names its tenant (the QoS class every submit on that connection runs
under), and either side leaves with GOODBYE.

Threading: the engine is single-owner.  Per-client receive threads
only parse frames and enqueue ops; ONE pump (``step()`` /
``serve_forever``) owns the engine — it drains ops, steps the engine,
and sends STREAM frames from the engine's token callbacks.  All
shared gateway state is guarded by ``self._lock`` (lock-discipline
rule), and every thread registers with the Watchdog like the worker
pool's.

Replicated edge (PR 20): N gateways may front the SAME engine fleet
by sharing an :class:`~orion_tpu.orchestration.replica.EdgeCoordinator`
(``edge=`` argument).  Replicas heartbeat each other over peer ORTP
links (protocol v8, ``FRAME_REPLICA_HB``), push the live edge set to
clients (``FRAME_EDGE``), and keep engines single-owner: only the
lowest live replica's pump touches engines — the others forward
engine-mutating ops through the edge.  Routing is prefix-affine (the
prefix cache's chain-hash keys a rendezvous choice of engine, so warm
prefixes land on the engine holding their pages), and
:class:`GatewayClient` fails over to a surviving replica on socket
death, re-submitting in-flight requests idempotently (the edge's
request-id dedupe replays a completed-but-unacked final verbatim).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import pickle
import queue
import threading
import time
import zlib
from typing import Any, Dict, Optional

import numpy as np

from orion_tpu import obs
from orion_tpu.orchestration.remote import (FRAME_GOODBYE, FRAME_HELLO,
                                            PROTOCOL_VERSION,
                                            ProtocolError, PyTreeChannel,
                                            listen_socket)
from orion_tpu.orchestration.replica import (FRAME_EDGE, FRAME_REPLICA_HB,
                                             ReplicaLink,
                                             rendezvous_engine)
from orion_tpu.resilience import Watchdog
from orion_tpu.resilience.inject import InjectedFault, fault_point
from orion_tpu.rollout.continuous import (CompletedRequest,
                                          EngineOverloaded, StreamChunk)

_LOG = logging.getLogger(__name__)

# The serving-gateway frame family (PROTOCOL_VERSION 5).  Values are
# disjoint from the pool family in remote.py (0-6); kept in a separate
# range so a frame number in a log unambiguously names its family.
FRAME_SUBMIT = 16   # client → gateway: enqueue a generation request
FRAME_STREAM = 17   # gateway → client: token chunk / final / error
FRAME_CANCEL = 18   # client → gateway: abort an in-flight request

_FRAME_NAMES = {
    FRAME_HELLO: "HELLO", FRAME_GOODBYE: "GOODBYE",
    FRAME_SUBMIT: "SUBMIT", FRAME_STREAM: "STREAM",
    FRAME_CANCEL: "CANCEL", FRAME_REPLICA_HB: "REPLICA_HB",
    FRAME_EDGE: "EDGE",
}


class GatewayClosed(ConnectionError):
    """The gateway said GOODBYE (drain/preemption) or the channel
    died.  A ConnectionError subclass so existing handlers keep
    working; the distinct type lets a client tell a deliberate server
    drain from its own misuse of a closed handle."""


@dataclasses.dataclass
class StreamEvent:
    """Client-side view of one STREAM frame.

    ``tokens`` are the new completion tokens since the previous event
    for this request; ``restarted`` voids everything delivered before
    (server-side preemption restarted the stream).  The final event
    has ``done=True`` and either ``completed`` (success — full tokens
    + logprobs, identical to what in-process ``generate()`` returns)
    or ``error`` (an :class:`EngineOverloaded` for sheds, a string
    reason otherwise, e.g. ``"cancelled"``)."""

    req_id: int
    tokens: np.ndarray
    done: bool = False
    restarted: bool = False
    error: Optional[Any] = None
    completed: Optional[CompletedRequest] = None


def parse_tenant_spec(spec: str) -> Dict[str, dict]:
    """Parse a compact tenant-QoS spec string into configure_tenant
    kwargs: ``"paid:weight=4,rate=100;free:weight=1,max_queued=8"``
    → ``{"paid": {"weight": 4, "rate_limit": 100.0}, "free": {...}}``.
    Used by ``launch.py --serve`` so QoS envelopes need no config-file
    plumbing."""
    out: Dict[str, dict] = {}
    for part in filter(None, (p.strip() for p in spec.split(";"))):
        name, sep, kvs = part.partition(":")
        if not sep or not name.strip():
            # A typo'd part ("paid=4,rate=100", missing colon) must
            # fail loudly — silently registering a tenant literally
            # named "paid=4,rate=100" with default QoS leaves the real
            # tenant unlimited.
            raise ValueError(
                f"tenant spec part {part!r} must look like "
                "'name:key=value,...' (missing ':')")
        kw: dict = {}
        for kv in filter(None, (s.strip() for s in kvs.split(","))):
            key, _, val = kv.partition("=")
            key = {"rate": "rate_limit"}.get(key.strip(), key.strip())
            if key in ("weight", "max_queued", "max_running"):
                kw[key] = int(val)
            elif key in ("rate_limit", "burst"):
                kw[key] = float(val)
            else:
                raise ValueError(f"unknown tenant-spec key {key!r} in "
                                 f"{part!r}")
        out[name.strip()] = kw
    return out


class _Client:
    """Gateway-side record of one connected client."""

    def __init__(self, cid: int, name: str, tenant: str,
                 chan: PyTreeChannel, hb):
        self.cid = cid
        self.name = name
        self.tenant = tenant
        self.chan = chan
        self.hb = hb
        self.alive = True
        self.reqs: Dict[int, int] = {}  # client req id -> engine rid


class ServingGateway:
    """Network front door for one :class:`ContinuousBatchingEngine`.

    The engine must already have weights loaded and an RNG seeded
    (``load_weights`` + ``reset_rng``).  ``tenants`` maps tenant name
    → ``configure_tenant`` kwargs (weight / rate_limit / burst /
    max_queued); unknown tenants connect with default QoS.  Drive the
    serve loop either with :meth:`serve_forever` (blocking; pass a
    ``stop`` event) or :meth:`start`/:meth:`close` (background pump
    thread — the in-process test harness)."""

    def __init__(self, engine, port: int = 0, host: str = "localhost",
                 tenants: Optional[Dict[str, dict]] = None,
                 recv_deadline: float = 0.0, tracer=None,
                 idle_wait: float = 0.002, autopilot=None,
                 prefill_tier=None, edge=None, affinity: bool = True):
        # Fleet front door (PR 18): ``engine`` may be one engine or a
        # sequence.  Requests route to the least-loaded ADMITTING
        # engine; the rollout coordinator gates engines out via
        # set_engine_admit while it drains/reloads them, and the
        # gateway routes around them so observed availability never
        # drops.  ``self.engine`` stays the primary (autopilot signals,
        # prefill tier, single-engine callers unchanged).
        #
        # Replicated edge (PR 20): pass a shared EdgeCoordinator as
        # ``edge`` and this gateway becomes one replica of it —
        # engines come FROM the edge, admission/rollout state is
        # fleet-shared, and only the owning replica's pump steps
        # engines.  ``affinity`` arms prefix-affine routing (multi-
        # engine fleets only; falls back to least-pending).
        self.edge = edge
        if edge is not None:
            engine = edge.engines
        self.engines = (list(engine) if isinstance(engine, (list, tuple))
                        else [engine])
        self.engine = self.engines[0]
        self._admit_ok = [True] * len(self.engines)
        self._affinity = bool(affinity)
        #: Routing decision log, primitive tuples ``(creq, affine_idx
        #: or -1, chosen_idx)`` in submit order — the witness the
        #: affinity-determinism test compares across seeded runs.
        #: Owner-pump-thread only; bounded.
        self.route_log: list = []
        #: WeightRolloutCoordinator attaches itself here; the pump
        #: drives its ticks (single engine-owner thread).  With an
        #: edge this is a write-through to ``edge.rollout`` so the
        #: roll survives the attaching replica's death.
        self._rollout = None
        self.host = host
        self._tracer = tracer
        self._idle_wait = idle_wait
        # Optional disaggregated prefill tier (PR 17): a
        # PrefillTierCoordinator fronting a PrefillWorker process.
        # Submits route through it (KV arrives pre-computed, the
        # engine prefix-hits it) and the pump drives its EDF
        # admissions; sheds from the DEFERRED engine.submit come back
        # through _on_tier_shed so the client still gets its typed
        # overloaded/bad-request STREAM frame.
        self.prefill_tier = prefill_tier
        if prefill_tier is not None and prefill_tier.on_shed is None:
            prefill_tier.on_shed = self._on_tier_shed
        # Optional SLO autopilot (orchestration.autopilot): the pump
        # loop is its cadence source, so one thread owns both the
        # engine AND every setpoint/QoS actuation — no locking between
        # controller and serving.
        self.autopilot = autopilot
        self.recv_deadline = recv_deadline
        for name, kw in (tenants or {}).items():
            for eng in self.engines:
                eng.configure_tenant(name, **kw)
        self.watchdog = Watchdog()
        self._lock = threading.Lock()
        self._clients: Dict[int, _Client] = {}
        self._next_cid = 0
        self._next_rid = 0
        # engine rid -> {"client", "creq", "eng" (engine index),
        # "p" (the submit payload, retained so a drain-deadline
        # migration can resubmit on another engine)}
        self._live: Dict[int, dict] = {}
        self._ops: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._pump_thread: Optional[threading.Thread] = None
        self.stats = {"submits": 0, "sheds": 0, "cancels": 0,
                      "clients_joined": 0, "clients_left": 0,
                      "resumes": 0, "dedupe_hits": 0,
                      "affinity_hits": 0, "affinity_misses": 0}

        self._srv = listen_socket(port, host=host)
        self.port = self._srv.getsockname()[1]
        accept_hb = self.watchdog.register("gw-accept", timeout=0.0)
        self._accept_thread = threading.Thread(
            target=self._accept_loop, args=(accept_hb,),
            name="gw-accept", daemon=True)
        self._accept_thread.start()

        # Join the edge LAST (port is bound, accept loop is up): dial
        # a peer link to every already-live replica — they hold the
        # accepted end — and start beating.
        self.replica_id = -1
        self._links: Dict[int, ReplicaLink] = {}
        if edge is not None:
            self.replica_id = edge.register(self)
            self._edge_seen = edge.version
            self._next_hb = 0.0
            for rid, gw_port in edge.live_ports():
                if rid != self.replica_id:
                    self._connect_link(rid, gw_port)

    # -- fleet-shared rollout attach point -------------------------------
    @property
    def rollout(self):
        return self.edge.rollout if self.edge is not None else \
            self._rollout

    @rollout.setter
    def rollout(self, value) -> None:
        if self.edge is not None:
            self.edge.rollout = value
        else:
            self._rollout = value

    # -- membership ------------------------------------------------------
    def _accept_loop(self, hb) -> None:
        import socket as _socket

        while not self._stop.is_set():
            hb.beat()
            try:
                conn, addr = self._srv.accept()
            except _socket.timeout:
                continue
            except OSError as e:
                if self._stop.is_set():
                    return
                _LOG.warning("gateway accept error (transient): %r", e)
                time.sleep(0.1)
                continue
            # Admission runs in a short-lived per-connection thread,
            # exactly like the worker pool's: _admit blocks on the
            # peer's HELLO (deadlined, floor 10 s), and ONE silent
            # stray parked in that handshake must not serialize every
            # healthy client behind it in the accept backlog.
            threading.Thread(  # orion: ignore[unsupervised-thread] handshake thread is strictly deadlined (recv deadline >= 10s), not a long-lived worker
                target=self._admit_conn, args=(conn, addr),
                name=f"gw-admit-{addr[1] if len(addr) > 1 else addr}",
                daemon=True).start()

    def _admit_conn(self, conn, addr) -> None:
        try:
            self._admit(conn)
        except (ProtocolError, ConnectionError, TimeoutError,
                pickle.UnpicklingError, OSError) as e:
            _LOG.warning("gateway refused a peer at %s: %s", addr, e)
            try:
                conn.close()
            except OSError:
                pass

    def _admit(self, conn) -> None:
        chan = PyTreeChannel(conn, recv_deadline=max(
            self.recv_deadline, 10.0) if self.recv_deadline else 10.0,
            tracer=self._tracer)
        kind, hello = chan.recv_frame()
        if kind != FRAME_HELLO:
            raise ProtocolError(
                f"expected HELLO, got {_FRAME_NAMES.get(kind, kind)}")
        if str(hello.get("role", "client")) == "replica":
            # Peer gateway replica dialling its membership link — a
            # different admission path entirely (no tenant, no client
            # record, just the liveness channel).
            self._admit_replica(chan, hello)
            return
        chan.set_recv_deadline(self.recv_deadline)
        tenant = str(hello.get("tenant", "default"))
        with self._lock:
            cid = self._next_cid
            self._next_cid += 1
        name = str(hello.get("name", f"client-{cid}"))
        ack = {"cid": cid, "protocol": PROTOCOL_VERSION,
               "tenant": tenant}
        if self.edge is not None:
            # The client learns the live edge set at admission (and on
            # every change via FRAME_EDGE) — the failover target list.
            ack["edge"] = self.edge.live_ports()
        chan.send_frame(FRAME_HELLO, ack)
        hb = self.watchdog.register(f"gw-client-{cid}", timeout=0.0)
        client = _Client(cid, name, tenant, chan, hb)
        thread = threading.Thread(
            target=self._recv_loop, args=(client,),
            name=f"gw-recv-{cid}", daemon=True)
        with self._lock:
            admitted = not self._stop.is_set()
            if admitted:
                self._clients[cid] = client
                self.stats["clients_joined"] += 1
        if not admitted:
            # close() raced the (threaded) handshake: release the peer
            # instead of registering a client nobody will ever drop.
            self.watchdog.unregister(hb.name)
            try:
                chan.send_frame(FRAME_GOODBYE, {"reason": "shutdown"})
            except (ConnectionError, TimeoutError, OSError):
                pass
            chan.close()
            return
        thread.start()
        if obs.get_tracer().enabled:
            obs.instant("gw.client-join", cid=cid, tenant=tenant)
        _LOG.info("gateway admitted %s (tenant=%s) as cid=%d",
                  name, tenant, cid)

    # -- replica membership links (PR 20) --------------------------------
    def _connect_link(self, rid: int, gw_port: int) -> None:
        """Dial the membership link to an already-live peer replica
        (constructor context; the peer's accept loop is up)."""
        chan = PyTreeChannel.connect(
            gw_port, host=self.host, timeout=10.0,
            recv_deadline=self.edge.link_deadline, tracer=self._tracer)
        chan.send_frame(FRAME_HELLO,
                        {"role": "replica",
                         "replica_id": self.replica_id,
                         "port": self.port,
                         "protocol": PROTOCOL_VERSION})
        kind, ack = chan.recv_frame()
        if kind != FRAME_HELLO:
            chan.close()
            raise ProtocolError(
                f"expected replica HELLO ack, got "
                f"{_FRAME_NAMES.get(kind, kind)}")
        self._start_link(ReplicaLink(rid, chan))

    def _admit_replica(self, chan, hello: dict) -> None:
        """Accepted end of a peer's membership link."""
        if self.edge is None:
            raise ProtocolError(
                "replica HELLO at a gateway with no edge attached")
        peer = int(hello["replica_id"])
        chan.set_recv_deadline(self.edge.link_deadline)
        chan.send_frame(FRAME_HELLO,
                        {"replica_id": self.replica_id,
                         "protocol": PROTOCOL_VERSION})
        self._start_link(ReplicaLink(peer, chan))
        if obs.get_tracer().enabled:
            obs.instant("gw.replica-join", rid=peer,
                        at=self.replica_id)

    def _start_link(self, link: ReplicaLink) -> None:
        with self._lock:
            self._links[link.rid] = link
        hb = self.watchdog.register(
            f"gw{self.replica_id}-link-{link.rid}", timeout=0.0)
        threading.Thread(
            target=self._link_recv_loop, args=(link, hb),
            name=f"gw{self.replica_id}-link-{link.rid}",
            daemon=True).start()

    def _link_recv_loop(self, link: ReplicaLink, hb) -> None:
        """One thread per peer link: count beats, watch for death.
        Link death IS the failure detector — a dead socket, a recv
        deadline (frozen peer) or a GOODBYE all become a replica-down
        op for the pump."""
        try:
            while not self._stop.is_set() and link.alive:
                hb.beat()
                kind, payload = link.chan.recv_frame()
                if kind == FRAME_REPLICA_HB:
                    link.beats_seen += 1
                elif kind == FRAME_GOODBYE:
                    self._ops.put(("replica-down", None, link.rid))
                    return
                else:
                    raise ProtocolError(
                        f"unexpected {_FRAME_NAMES.get(kind, kind)} "
                        "frame on a replica membership link")
        except (ConnectionError, TimeoutError, OSError, EOFError,
                pickle.UnpicklingError, ProtocolError):
            self._ops.put(("replica-down", None, link.rid))
        finally:
            self.watchdog.unregister(hb.name)

    def _recv_loop(self, client: _Client) -> None:
        """One thread per client: parse frames, enqueue ops.  The pump
        thread owns the engine — nothing here touches it."""
        try:
            while not self._stop.is_set():
                client.hb.beat()
                kind, payload = client.chan.recv_frame()
                if kind == FRAME_SUBMIT:
                    self._ops.put(("submit", client, payload))
                elif kind == FRAME_CANCEL:
                    self._ops.put(("cancel", client, payload))
                elif kind == FRAME_GOODBYE:
                    self._ops.put(("leave", client, None))
                    return
                else:
                    raise ProtocolError(
                        f"unexpected {_FRAME_NAMES.get(kind, kind)} "
                        "frame from gateway client")
        except (ConnectionError, TimeoutError, OSError, EOFError,
                pickle.UnpicklingError) as e:
            # Dropped client: the pump cancels its in-flight work.
            self._ops.put(("leave", client, repr(e)))

    # -- pump (single engine owner) --------------------------------------
    def _send_stream(self, client: _Client, payload: dict) -> None:
        if not client.alive:
            return
        try:
            client.chan.send_frame(FRAME_STREAM, payload)
        except (ConnectionError, TimeoutError, OSError) as e:
            _LOG.warning("gateway send to cid=%d failed: %r",
                         client.cid, e)
            # May be running INSIDE engine.step() (token callback):
            # _drop_client defers the engine-side aborts to the next
            # pump iteration, so the engine is never mutated
            # re-entrantly mid-wave.
            self._drop_client(client)

    def _on_chunk(self, client: _Client, creq: int,
                  chunk: StreamChunk) -> None:
        """Engine token callback (runs inside engine.step() on the
        pump thread): fan the chunk out as a STREAM frame."""
        payload: dict = {"req": creq, "tokens": chunk.tokens,
                         "done": chunk.done,
                         "restarted": chunk.restarted}
        if chunk.done:
            comp = chunk.completed
            payload["final_tokens"] = comp.tokens
            payload["logprobs"] = comp.logprobs
            payload["policy_logprobs"] = comp.policy_logprobs
            with self._lock:
                self._live.pop(client.reqs.pop(creq, None), None)
            if self.edge is not None:
                # Retain the final BEFORE attempting the send: if the
                # send fails (client mid-failover) the resume replays
                # this exact payload instead of re-executing.
                self.edge.record_done((client.name, creq), payload)
        self._send_stream(client, payload)

    # -- fleet routing (PR 18) -------------------------------------------
    def set_engine_admit(self, idx: int, ok: bool) -> None:
        """Admission gate for one engine of the fleet: a gated engine
        receives no NEW submits (in-flight decoding continues).  The
        rollout coordinator's DRAINING/READMIT actuator.  With an
        edge the gate is FLEET-SHARED: gating through any one replica
        gates the engine at every replica — a weight roll coordinates
        admission across the whole edge for free."""
        if self.edge is not None:
            self.edge.set_admit(idx, ok)
            return
        with self._lock:
            self._admit_ok[idx] = bool(ok)

    def engine_admitting(self, idx: int) -> bool:
        if self.edge is not None:
            return self.edge.admitting(idx)
        with self._lock:
            return self._admit_ok[idx]

    def _route_order(self, exclude: Optional[int] = None) -> list:
        """Admitting engine indices, least-pending first (ties by
        index — deterministic under seeded replay)."""
        if self.edge is not None:
            ok = self.edge.admit_snapshot()
        else:
            with self._lock:
                ok = list(self._admit_ok)
        return sorted(
            (i for i in range(len(self.engines))
             if ok[i] and i != exclude),
            key=lambda i: (self.engines[i].pending, i))

    def _affine_engine(self, p: dict) -> Optional[int]:
        """Prefix-affinity key → engine index, or None (affinity off,
        single engine, prompt shorter than one page, prefix cache
        disabled, or an injected ``gateway.route`` fault).  The key is
        the FIRST page's chain-hash — exactly the hash the prefix
        cache keys its pages by — so every request sharing a template
        prefix maps to the SAME engine, the one holding the warm
        pages.  Fail-open: a routing fault degrades to least-pending,
        never to a dropped request."""
        if not self._affinity or len(self.engines) < 2:
            return None
        try:
            fault_point("gateway.route")
            hashes = self.engine._page_hashes(
                np.asarray(p["ids"], np.int32))
        except InjectedFault:
            return None
        if not hashes:
            return None
        return rendezvous_engine(hashes[0], len(self.engines))

    def _submit_routed(self, client: _Client, creq: int, rid: int,
                       p: dict, exclude: Optional[int] = None) -> None:
        """Submit ``p`` on the first admitting engine that accepts it.
        Prefix-affine first — the rendezvous-chosen engine leads the
        order unless it is gated, excluded, or draining — then least-
        pending: an overload shed from the affine engine falls
        through to the siblings, so affinity never costs availability.
        A shed from EVERY admitting engine — or an empty route (whole
        fleet gated) — propagates as the typed EngineOverloaded; a
        ValueError (malformed request) is the client's own and is
        never retried on a sibling."""
        order = self._route_order(exclude=exclude)
        if not order:
            raise EngineOverloaded(
                "no engine admitting (fleet draining)",
                queue_depth=sum(e.pending for e in self.engines),
                retry_after=0.25, tenant=client.tenant)
        aff = self._affine_engine(p)
        if aff is not None and aff in order \
                and not self.engines[aff].draining:
            order.remove(aff)
            order.insert(0, aff)
        last: Optional[EngineOverloaded] = None
        for idx in order:
            try:
                self.engines[idx].submit(
                    rid, np.asarray(p["ids"], np.int32),
                    budget=p.get("budget"),
                    priority=int(p.get("priority", 0)),
                    deadline=p.get("deadline"),
                    tenant=client.tenant, stream=True,
                    on_tokens=lambda chunk, c=client, q=creq:
                        self._on_chunk(c, q, chunk))
            except EngineOverloaded as e:
                last = e
                continue
            with self._lock:
                client.reqs[creq] = rid
                self._live[rid] = {"client": client, "creq": creq,
                                   "eng": idx, "p": p}
                if aff is not None:
                    self.stats["affinity_hits" if idx == aff
                               else "affinity_misses"] += 1
            self.route_log.append(
                (int(creq), -1 if aff is None else int(aff), int(idx)))
            if len(self.route_log) > 8192:
                del self.route_log[:4096]
            if self.edge is not None:
                self.edge.mark_inflight((client.name, creq),
                                        self.replica_id, idx, rid)
            return
        raise last

    def _alloc_rid(self) -> int:
        """Engine request id for a new submit.  With an edge the id
        comes from the fleet-shared counter — N replicas submit to
        the SAME engines, so per-gateway counters would collide on
        the engine's request-id space."""
        if self.edge is not None:
            return self.edge.alloc_req_id()
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        return rid

    def _apply_resume(self, client: _Client, creq: int) -> bool:
        """Idempotent failover re-submit (``resume`` flag on SUBMIT).
        Returns True when fully handled — the request had COMPLETED on
        the engine before the client's old replica died, so the
        retained final frame replays verbatim: bit-identical tokens,
        no re-execution, no double-billing.  Otherwise any engine-side
        leftover of the old attempt is cancelled, a RESTARTED marker
        voids the client's partial delivery, and the caller falls
        through to a fresh routed submit."""
        key = (client.name, creq)
        rec = self.edge.lookup(key)
        if rec is not None and rec.get("done"):
            with self._lock:
                self.stats["dedupe_hits"] += 1
            # Replay the retained final as ONE restarted full-stream
            # frame: chunks the dead replica never delivered would
            # leave a gap in the client's incremental stream, so the
            # RESTARTED marker voids its partials and ``tokens``
            # carries the COMPLETE list — bit-identical to the
            # original completion, engine never re-executed.
            payload = rec["payload"]
            self._send_stream(client, {
                **payload, "tokens": payload["final_tokens"],
                "restarted": True})
            return True
        if rec is not None:
            # Still in flight from the old connection: take it over.
            if self.prefill_tier is not None:
                self.prefill_tier.cancel(rec["rid"])
            try:
                self.engines[rec["eng"]].cancel(rec["rid"])
            except (KeyError, ValueError):
                pass
            gw = self.edge.replica(rec["replica"])
            if gw is not None:
                with gw._lock:
                    gw._live.pop(rec["rid"], None)
            self.edge.forget(key)
        with self._lock:
            self.stats["resumes"] += 1
        self._send_stream(client, {
            "req": creq, "tokens": np.empty(0, np.int32),
            "done": False, "restarted": True})
        return False

    def _apply_submit(self, client: _Client, p: dict) -> None:
        creq = int(p["req"])
        if self.edge is not None and p.get("resume") \
                and self._apply_resume(client, creq):
            return
        with self._lock:
            duplicate = creq in client.reqs
        if duplicate:
            self._send_stream(client, {
                "req": creq, "done": True, "tokens": np.empty(0, np.int32),
                "error": "bad-request",
                "message": f"request id {creq} already in flight"})
            return
        rid = self._alloc_rid()
        if self.prefill_tier is not None and self.engine_admitting(0):
            # Tier route (primary engine only — the tier's KV lands in
            # engine 0's cache): the request is live from the client's
            # view the moment it parks tier-side; engine admission
            # (and any shed) happens at the pump that sees its KV
            # arrive, and comes back through _on_tier_shed.  While
            # engine 0 drains for a weight roll, submits skip the tier
            # and route directly to a sibling.
            with self._lock:
                client.reqs[creq] = rid
                self._live[rid] = {"client": client, "creq": creq,
                                   "eng": 0, "p": p}
                self.stats["submits"] += 1
            if self.edge is not None:
                self.edge.mark_inflight((client.name, creq),
                                        self.replica_id, 0, rid)
            self.prefill_tier.submit(
                rid, np.asarray(p["ids"], np.int32),
                budget=p.get("budget"),
                priority=int(p.get("priority", 0)),
                deadline=p.get("deadline"),
                tenant=client.tenant, stream=True,
                on_tokens=lambda chunk, c=client, q=creq:
                    self._on_chunk(c, q, chunk))
            return
        try:
            self._submit_routed(client, creq, rid, p)
            with self._lock:
                self.stats["submits"] += 1
        except EngineOverloaded as e:
            # Typed backpressure crosses the wire: depth + retry hint
            # ride the error payload and the client re-raises the same
            # EngineOverloaded type.
            with self._lock:
                self.stats["sheds"] += 1
            self._send_stream(client, {
                "req": creq, "done": True,
                "tokens": np.empty(0, np.int32), "error": "overloaded",
                "message": str(e), "queue_depth": e.queue_depth,
                "retry_after": e.retry_after, "tenant": e.tenant})
        except ValueError as e:
            self._send_stream(client, {
                "req": creq, "done": True,
                "tokens": np.empty(0, np.int32),
                "error": "bad-request", "message": str(e)})

    def _on_tier_shed(self, rid: int, exc: Exception) -> None:
        """Deferred-admission failure from the prefill tier's pump:
        the engine refused the request AFTER its KV came back.  The
        client gets the same typed STREAM error the direct path sends
        synchronously."""
        with self._lock:
            entry = self._live.pop(rid, None)
        if entry is None:
            return  # client already gone
        client, creq = entry["client"], entry["creq"]
        with self._lock:
            client.reqs.pop(creq, None)
        if self.edge is not None:
            self.edge.forget((client.name, creq))
        if isinstance(exc, EngineOverloaded):
            with self._lock:
                self.stats["sheds"] += 1
            self._send_stream(client, {
                "req": creq, "done": True,
                "tokens": np.empty(0, np.int32), "error": "overloaded",
                "message": str(exc), "queue_depth": exc.queue_depth,
                "retry_after": exc.retry_after, "tenant": exc.tenant})
        else:
            self._send_stream(client, {
                "req": creq, "done": True,
                "tokens": np.empty(0, np.int32),
                "error": "bad-request", "message": str(exc)})

    def _apply_cancel(self, client: _Client, p: dict) -> None:
        creq = int(p["req"])
        with self._lock:
            rid = client.reqs.get(creq)
            entry = self._live.get(rid) if rid is not None else None
            eng = self.engines[entry["eng"]] if entry is not None \
                else self.engine
        if rid is None:
            return  # finished (or never existed): cancel is a no-op
        if self.prefill_tier is not None:
            # Still parked tier-side?  Forget it there too; the
            # engine-side cancel below is then the no-op.
            self.prefill_tier.cancel(rid)
        try:
            eng.cancel(rid)
        except KeyError:
            pass
        with self._lock:
            self._live.pop(rid, None)
            client.reqs.pop(creq, None)
            self.stats["cancels"] += 1
        if self.edge is not None:
            self.edge.forget((client.name, creq))
        self._send_stream(client, {
            "req": creq, "done": True, "tokens": np.empty(0, np.int32),
            "error": "cancelled", "message": "cancelled by client"})

    def _drop_client(self, client: _Client, goodbye: bool = False) -> None:
        with self._lock:
            if not client.alive:
                return
            client.alive = False
            gone = list(client.reqs.items())  # (creq, rid)
            client.reqs.clear()
            reap = []
            for _creq, rid in gone:
                entry = self._live.pop(rid, None)
                reap.append((rid, entry["eng"] if entry else 0))
            self.stats["clients_left"] += 1
        if self.edge is not None:
            # Forget the IN-FLIGHT dedupe records (the work is about
            # to be reaped); retained DONE records stay — a failover
            # reconnect of this same logical client replays them.
            for creq, _rid in gone:
                self.edge.forget((client.name, creq))
        self.watchdog.unregister(client.hb.name)
        if reap:
            # Deferred to the next pump iteration: this method can run
            # inside engine.step() (a send failing from a token
            # callback), where an inline engine.cancel would mutate
            # engine state mid-wave.
            self._ops.put(("reap", None, reap))
        if goodbye:
            try:
                client.chan.send_frame(FRAME_GOODBYE,
                                       {"reason": "shutdown"})
            except (ConnectionError, TimeoutError, OSError):
                pass
        try:
            client.chan.close()
        except OSError:
            pass
        if obs.get_tracer().enabled:
            obs.instant("gw.client-leave", cid=client.cid)

    def migrate_engine_requests(self, idx: int) -> int:
        """Drain-deadline actuator (pump-owner context only): move
        every in-flight request off engine ``idx`` — cancel it there,
        stream a typed RESTARTED marker (the client voids everything
        delivered so far, exactly like a preemption restart), and
        resubmit the retained payload on a sibling engine.  The client
        request never drops: it either readmits elsewhere or gets the
        normal typed overloaded/bad-request error.  With an edge this
        sweeps EVERY live replica's in-flight set (the rollout
        coordinator calls through one gateway but the whole edge has
        requests on the draining engine).  Returns how many requests
        moved."""
        if self.edge is not None:
            return sum(gw._migrate_local(idx)
                       for gw in self.edge.live_replicas())
        return self._migrate_local(idx)

    def _migrate_local(self, idx: int) -> int:
        with self._lock:
            victims = [(rid, dict(e)) for rid, e in self._live.items()
                       if e["eng"] == idx]
        moved = 0
        for rid, entry in sorted(victims):
            client, creq, p = entry["client"], entry["creq"], entry["p"]
            if self.prefill_tier is not None:
                self.prefill_tier.cancel(rid)
            try:
                self.engines[idx].cancel(rid)
            except (KeyError, ValueError):
                pass
            with self._lock:
                self._live.pop(rid, None)
                client.reqs.pop(creq, None)
            # The restart marker precedes the new engine's chunks, so
            # the client discards the old engine's partial delivery.
            self._send_stream(client, {
                "req": creq, "tokens": np.empty(0, np.int32),
                "done": False, "restarted": True})
            new_rid = self._alloc_rid()
            try:
                self._submit_routed(client, creq, new_rid, p,
                                    exclude=idx)
                moved += 1
            except EngineOverloaded as e:
                with self._lock:
                    self.stats["sheds"] += 1
                if self.edge is not None:
                    self.edge.forget((client.name, creq))
                self._send_stream(client, {
                    "req": creq, "done": True,
                    "tokens": np.empty(0, np.int32),
                    "error": "overloaded", "message": str(e),
                    "queue_depth": e.queue_depth,
                    "retry_after": e.retry_after, "tenant": e.tenant})
            except ValueError as e:
                self._send_stream(client, {
                    "req": creq, "done": True,
                    "tokens": np.empty(0, np.int32),
                    "error": "bad-request", "message": str(e)})
        return moved

    # -- edge membership duties (every replica's pump) -------------------
    def _edge_maintenance(self) -> None:
        """Heartbeat the peer links (wall-gated cadence — liveness is
        inherently wall-time; every membership DECISION is driven by
        link death / GOODBYE / injected faults, which is what keeps
        seeded replay bit-identical) and push FRAME_EDGE to clients
        when the live set changed.  A failed or injected beat IS the
        failure detector firing: the link drops and the peer is
        presumed dead — the shared edge then demotes it rather than
        split-braining (see replica.py)."""
        edge = self.edge
        now = edge.clock()
        if now >= self._next_hb:
            self._next_hb = now + edge.hb_interval
            with self._lock:
                links = list(self._links.items())
            for rid, link in links:
                if not link.alive:
                    continue
                try:
                    fault_point("replica.heartbeat")
                    link.chan.send_frame(
                        FRAME_REPLICA_HB,
                        {"rid": self.replica_id,
                         "owner": edge.owner_id()})
                except (InjectedFault, ConnectionError, TimeoutError,
                        OSError):
                    self._replica_down(rid)
        ver = edge.version
        if ver != self._edge_seen:
            self._edge_seen = ver
            payload = {"edge": edge.live_ports()}
            with self._lock:
                clients = [c for c in self._clients.values() if c.alive]
            for c in clients:
                try:
                    c.chan.send_frame(FRAME_EDGE, payload)
                except (ConnectionError, TimeoutError, OSError):
                    self._drop_client(c)

    def _replica_down(self, rid: int) -> None:
        if rid == self.replica_id:
            return
        with self._lock:
            link = self._links.pop(rid, None)
        if link is not None:
            link.alive = False
            try:
                link.chan.close()
            except OSError:
                pass
        # A link death is SYMMETRIC: both ends observe it and each
        # presumes the other dead.  The shared edge serializes the
        # argument — first accusation wins; a replica the membership
        # already demoted lost it, and its counter-accusation is
        # discarded (otherwise one dropped link would take BOTH
        # replicas out and strand the engines ownerless).
        if not self.edge.is_live(self.replica_id):
            return
        if self.edge.peer_down(rid):
            _LOG.warning("gateway replica %d presumed dead "
                         "(observed by replica %d)", rid,
                         self.replica_id)
            if obs.get_tracer().enabled:
                obs.instant("gw.replica-down", rid=rid,
                            by=self.replica_id,
                            owner=self.edge.owner_id())

    def _adopt_dead(self, dead_rid: int) -> None:
        """Owner-pump duty after a replica death: cancel the dead
        replica's engine-side work (its clients are failing over and
        will re-submit through a survivor — the resume path replays
        completed finals and re-runs the rest) and forget its
        in-flight dedupe records so those resumes take the fresh
        path."""
        gw = self.edge.replica(dead_rid)
        if gw is None or gw is self:
            return
        with gw._lock:
            victims = list(gw._live.items())
            gw._live.clear()
            for c in gw._clients.values():
                c.reqs.clear()
                c.alive = False
        reaps = [(rid, entry["eng"]) for rid, entry in victims]
        forget = [(entry["client"].name, entry["creq"])
                  for _rid, entry in victims]
        # Reap ops parked in the dead pump's queue (a client drop it
        # never got to apply) would otherwise leak decoding forever.
        while True:
            try:
                op, _client, payload = gw._ops.get_nowait()
            except queue.Empty:
                break
            if op == "reap":
                reaps.extend(payload)
        for rid, eng in sorted(reaps):
            if self.prefill_tier is not None:
                self.prefill_tier.cancel(rid)
            try:
                self.engines[eng].cancel(rid)
            except (KeyError, ValueError):
                pass
        for key in forget:
            self.edge.forget(key)
        if obs.get_tracer().enabled:
            obs.instant("gw.replica-adopt", rid=dead_rid,
                        by=self.replica_id, reaped=len(reaps))

    def _fence(self) -> None:
        """The membership presumed THIS replica dead — a peer won the
        link-death accusation race, or our own heartbeats stopped
        landing — while we are in fact still running.  The owner is
        concurrently adopting our engine-side work, so continuing to
        serve would hand our clients silent drops (their completions
        now fan out through nobody).  Fence instead: GOODBYE + close
        every client channel (they fail over to a live replica and
        resume idempotently), drop the peer links, stop the pump.
        Engines are never touched from here — they belong to the
        owner."""
        if self._stop.is_set():
            return
        _LOG.warning("gateway replica %d fenced (membership presumed "
                     "it dead); dropping clients for failover",
                     self.replica_id)
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        with self._lock:
            clients = list(self._clients.values())
            self._clients.clear()
            links = list(self._links.values())
            self._links.clear()
        for c in clients:
            # NOT _drop_client: adoption may already have flagged the
            # client dead gateway-side, but its socket is still open —
            # the GOODBYE is what turns a silent hang into a failover.
            c.alive = False
            try:
                c.chan.send_frame(FRAME_GOODBYE,
                                  {"reason": "replica fenced"})
            except (ConnectionError, TimeoutError, OSError):
                pass
            try:
                c.chan.close()
            except OSError:
                pass
            self.watchdog.unregister(c.hb.name)
        for link in links:
            link.alive = False
            try:
                link.chan.close()
            except OSError:
                pass
        if obs.get_tracer().enabled:
            obs.instant("gw.replica-fenced", rid=self.replica_id)

    def kill(self) -> None:
        """Chaos actuator: simulated SIGKILL of this replica.  Stops
        the pump and accept loops and closes EVERY socket abruptly —
        no GOODBYEs, no reaping, no edge departure.  Survivor
        replicas detect the death through their membership links (and
        adopt the orphaned engine work); clients see the socket die
        and fail over.  In-process limitation: the pump thread
        finishes its current iteration before the join (a real
        SIGKILL would also take the engines down — here they are the
        shared fleet and survive, which is the scenario under test:
        losing the EDGE, not the fleet)."""
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5.0)
            self._pump_thread = None
        with self._lock:
            clients = list(self._clients.values())
            links = list(self._links.values())
        for c in clients:
            c.alive = False
            try:
                c.chan.close()
            except OSError:
                pass
        for link in links:
            link.alive = False
            try:
                link.chan.close()
            except OSError:
                pass
        if self._accept_thread.is_alive():
            self._accept_thread.join(timeout=2.0)

    def _is_owner(self) -> bool:
        """Engine-owner check: without an edge this gateway IS the
        owner; with one, ownership follows the lowest live replica id
        (transferring automatically when the owner dies)."""
        return self.edge is None or \
            self.edge.owner_id() == self.replica_id

    def _apply_op(self, op, client, payload, owner: bool) -> None:
        """Apply one queued op.  A NON-owner replica forwards every
        engine-mutating op to the owner's pump through the edge
        (engines stay single-owner); client-local ops (leave) and
        membership ops apply anywhere."""
        if not owner and op in ("submit", "cancel", "reap"):
            self.edge.fleet_ops.put((op, client, payload, self))
            return
        if op == "submit":
            self._apply_submit(client, payload)
        elif op == "cancel":
            self._apply_cancel(client, payload)
        elif op == "leave":
            self._drop_client(client)
        elif op == "replica-down":
            self._replica_down(payload)
        elif op == "reap":
            # Engine-side aborts for a client dropped mid-wave —
            # applied here, OUTSIDE any engine.step().
            for rid, eng in payload:
                try:
                    self.engines[eng].cancel(rid)
                except (KeyError, ValueError):
                    pass
        else:  # pragma: no cover - internal op enum
            raise RuntimeError(f"unknown gateway op {op!r}")

    def step(self) -> int:
        """One pump iteration: apply queued client ops, tick the
        rollout coordinator (if attached), run one wave on every
        engine with work, fan out the resulting stream chunks (each
        engine fires the callbacks inside ``step()``).  Returns the
        number of requests still in flight fleet-wide.

        With an edge, a NON-owner replica only pumps its clients
        (forwarding engine ops to the owner) and its membership
        duties; the owner additionally adopts dead replicas' work,
        drains the fleet op queue, and runs the engines."""
        n = len(self._clients)  # orion: ignore[lock-discipline] a span label; len() of a dict is one atomic read
        with obs.span("gw.step", clients=n):
            return self._pump()

    def _pump(self) -> int:
        owner = self._is_owner()
        while True:
            try:
                op, client, payload = self._ops.get_nowait()
            except queue.Empty:
                break
            self._apply_op(op, client, payload, owner)
        if self.edge is not None:
            self._edge_maintenance()
            if self.replica_id >= 0 \
                    and not self.edge.is_live(self.replica_id):
                self._fence()
                return 0
            if not owner:
                return 0
            # Owner-only edge duties, ordered: first adopt any dead
            # replica's orphaned engine work (cancels free the pages
            # the resumes below re-claim), then apply ops forwarded
            # by the other replicas.
            for dead_rid in self.edge.take_reaps():
                self._adopt_dead(dead_rid)
            while True:
                try:
                    op, client, payload, gw = \
                        self.edge.fleet_ops.get_nowait()
                except queue.Empty:
                    break
                gw._apply_op(op, client, payload, True)
        if self.prefill_tier is not None:
            # EDF-admit every request whose prefilled KV arrived (or
            # cold-admit everything if the tier died) BEFORE the wave,
            # and surface the tier-labelled counters.
            self.prefill_tier.pump()
            with self._lock:
                self.stats.update({"prefill_" + k: v for k, v in
                                   self.prefill_tier.stats.items()})
        if self.rollout is not None:
            # Blue/green weight rollout (PR 18): the coordinator's
            # whole state machine runs on this thread — the single
            # engine owner — so drain checks, param swaps and canary
            # probes never race a wave.
            if self.rollout.tick():
                with self._lock:
                    self.stats.update(self.rollout.counters())
        for eng in self.engines:
            if eng.pending:
                eng.step()
        if self.autopilot is not None:
            # Wall-clock-gated inside: at most one decision per
            # cfg.controller.tick_interval regardless of pump rate.
            before = self.autopilot.ticks
            self.autopilot.maybe_tick()
            if self.autopilot.ticks != before:
                with self._lock:
                    self.stats.update(self.autopilot.counters())
        return int(sum(e.pending for e in self.engines))

    def serve_forever(self, stop: Optional[threading.Event] = None,
                      preemption=None, hb=None) -> None:
        """Blocking pump loop until ``stop`` is set (or ``preemption``
        — a resilience.preemption handler — requests exit)."""
        if hb is None:
            hb = self.watchdog.register("gw-pump", timeout=0.0)
        try:
            while not self._stop.is_set():
                hb.beat()
                if stop is not None and stop.is_set():
                    break
                if preemption is not None and preemption.requested:
                    break
                if self.step() == 0 and self._ops.empty():
                    # idle: nothing in flight, wait briefly for work
                    time.sleep(self._idle_wait)
        finally:
            self.watchdog.unregister(hb.name)

    def start(self) -> None:
        """Run :meth:`serve_forever` on a background pump thread (the
        in-process harness tests and benches drive)."""
        if self._pump_thread is not None:
            raise RuntimeError("gateway pump already started")
        pump_hb = self.watchdog.register("gw-pump", timeout=0.0)
        self._pump_thread = threading.Thread(
            target=self.serve_forever, kwargs={"hb": pump_hb},
            name="gw-pump", daemon=True)
        self._pump_thread.start()

    def close(self) -> None:
        """Stop the pump + accept loops, GOODBYE every client, abort
        their in-flight requests, close every channel.  The engine
        (caller-owned) is left intact — and DRAINED of this gateway's
        work: once the pump is joined this thread owns the engine, so
        the reap ops _drop_client enqueues are applied here instead of
        rotting in the queue (a caller re-fronting the engine must not
        inherit cancelled clients' decoding).  An edge replica leaves
        GRACEFULLY: GOODBYE on every peer link, then departs the
        membership — and if it is NOT the engine owner, its leftover
        reaps are forwarded to the owner instead of touching engines
        from this thread."""
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5.0)
            self._pump_thread = None
        with self._lock:
            clients = list(self._clients.values())
            links = list(self._links.values())
        for link in links:
            link.alive = False
            try:
                link.chan.send_frame(FRAME_GOODBYE,
                                     {"reason": "shutdown"})
            except (ConnectionError, TimeoutError, OSError):
                pass
            try:
                link.chan.close()
            except OSError:
                pass
        for c in clients:
            self._drop_client(c, goodbye=True)
        # Drain leftover ops (reaps from the drops above, plus
        # anything the pump never got to).  Submits are NOT applied —
        # their clients are gone.
        owner = self._is_owner()
        while True:
            try:
                op, _client, payload = self._ops.get_nowait()
            except queue.Empty:
                break
            if op == "reap":
                if not owner:
                    self.edge.fleet_ops.put(("reap", None, payload,
                                             self))
                    continue
                for rid, eng in payload:
                    try:
                        self.engines[eng].cancel(rid)
                    except (KeyError, ValueError):
                        pass
        if self.edge is not None:
            self.edge.leave(self.replica_id)
        if self._accept_thread.is_alive():
            self._accept_thread.join(timeout=2.0)


class GatewayClient:
    """Remote-client side of the gateway protocol.

    Connects, HELLOs with its tenant, then submits requests and reads
    :class:`StreamEvent` increments as the gateway fans them out.
    ``next_event`` blocks up to ``timeout``; an
    :class:`EngineOverloaded` shed arrives as an event whose ``error``
    IS that typed exception (depth + retry-after preserved), so a
    remote client backs off exactly like an in-process caller.

    Failover (PR 20): against a replicated edge the HELLO ack (and
    every FRAME_EDGE push) carries the live replica set.  When the
    connection dies — replica SIGKILL, drain GOODBYE — the client
    reconnects to the next live replica under seeded-jitter backoff
    and re-submits its in-flight requests with the ``resume`` flag:
    the edge's dedupe replays an already-completed final verbatim and
    restarts the rest via the RESTARTED-marker machinery, so the
    caller's event stream just continues.  ``failover=False`` (or an
    empty survivor set) restores the raise-``GatewayClosed``
    behavior."""

    #: Per-process default-name counter: dedupe keys are
    #: ``(client name, request id)`` at the edge, so two anonymous
    #: clients in one process must not collide.
    _NAME_SEQ = itertools.count()

    def __init__(self, port: int, host: str = "localhost",
                 tenant: str = "default", name: Optional[str] = None,
                 connect_timeout: float = 30.0,
                 recv_deadline: float = 0.0, tracer=None,
                 failover: bool = True):
        import os as _os

        self.tenant = str(tenant)
        self.name = name or (f"gw-client-{_os.getpid()}-"
                             f"{next(self._NAME_SEQ)}")
        self.closed = threading.Event()
        self._events: queue.Queue = queue.Queue()
        self._next_req = 0
        self.watchdog = Watchdog()
        self._host = host
        self._connect_timeout = connect_timeout
        self._recv_deadline = recv_deadline
        self._tracer = tracer
        self._failover_enabled = bool(failover)
        self._user_closed = False
        self.failovers = 0
        self._inflight: Dict[int, dict] = {}  # creq -> submit payload
        self._ilock = threading.Lock()
        self._folock = threading.Lock()
        #: Serializes event-queue REORDERING (failover's sentinel
        #: sweep, submit_with_backoff's foreign-event re-queue)
        #: against the recv thread's puts: stream order is the
        #: client's only restart-void signal, so a stashed RESTARTED
        #: marker re-queued behind later chunks would void the wrong
        #: prefix.
        self._eqlock = threading.Lock()
        self._connect(port)

    def _connect(self, port: int) -> None:
        """Dial + HELLO one replica and start its receive thread.
        Used by the constructor and by :meth:`_failover` (which
        replaces ``self.chan`` — the old receive thread notices and
        exits without poisoning the event queue)."""
        chan = PyTreeChannel.connect(
            port, host=self._host, timeout=self._connect_timeout,
            recv_deadline=self._recv_deadline, tracer=self._tracer)
        chan.send_frame(FRAME_HELLO,
                        {"name": self.name, "tenant": self.tenant,
                         "protocol": PROTOCOL_VERSION})
        kind, ack = chan.recv_frame()
        if kind == FRAME_GOODBYE:
            chan.close()
            raise ConnectionError(
                f"gateway refused {self.name}: "
                f"{ack.get('reason', 'no reason given')}")
        if kind != FRAME_HELLO:
            chan.close()
            raise ProtocolError(
                f"expected HELLO ack, got {_FRAME_NAMES.get(kind, kind)}")
        self.chan = chan
        self.cid = int(ack["cid"])
        self.port = int(port)
        #: Live replica ports, rid-ordered — the failover targets.
        #: A single un-replicated gateway hands back no edge; the
        #: list then holds just the dialled port.
        self.edge_ports = [int(p) for _rid, p in ack.get("edge", ())] \
            or [int(port)]
        # Re-arm BEFORE the receive thread starts: during a failover
        # ``closed`` is still set from the old channel's death, and the
        # recv loop gates on it — a thread that wins the race against a
        # caller-side clear would exit instantly, leaving the fresh
        # channel with no reader and the client hung.
        self.closed.clear()
        rx_hb = self.watchdog.register(
            f"gw-client-rx-{self.cid}-{self.failovers}", timeout=0.0)
        self._rx_thread = threading.Thread(
            target=self._recv_loop, args=(rx_hb, chan),
            name="gw-client-recv", daemon=True)
        self._rx_thread.start()

    #: Queue sentinel: the recv loop died (GOODBYE or channel error).
    #: Wakes any blocked ``next_event`` so a server drain surfaces as
    #: a typed :class:`GatewayClosed` instead of hanging forever (or
    #: until ``channel_recv_deadline``) in ``Queue.get``.
    _CLOSED = object()

    def _recv_loop(self, hb, chan) -> None:
        reason = "connection lost"
        try:
            while not self.closed.is_set() and chan is self.chan:
                hb.beat()
                kind, p = chan.recv_frame()
                if kind == FRAME_STREAM:
                    ev = self._to_event(p)
                    if ev.done:
                        # Settled (success OR typed error): no longer
                        # a failover re-submit candidate.
                        with self._ilock:
                            self._inflight.pop(ev.req_id, None)
                    with self._eqlock:
                        self._events.put(ev)
                elif kind == FRAME_EDGE:
                    self.edge_ports = [int(pt) for _rid, pt in
                                       p.get("edge", ())] \
                        or self.edge_ports
                elif kind == FRAME_GOODBYE:
                    reason = str(p.get("reason", "goodbye"))
                    break
                else:
                    raise ProtocolError(
                        f"unexpected {_FRAME_NAMES.get(kind, kind)} "
                        "frame from gateway")
        except (ConnectionError, TimeoutError, OSError, EOFError,
                pickle.UnpicklingError) as e:
            reason = repr(e)
        finally:
            self.watchdog.unregister(hb.name)
        if chan is self.chan:
            # Still the active channel (not replaced by a completed
            # failover): surface the close.  A superseded thread exits
            # silently — its sentinel would poison the fresh stream.
            self._close_reason = reason
            self.closed.set()
            self._events.put(self._CLOSED)

    @staticmethod
    def _to_event(p: dict) -> StreamEvent:
        error: Any = p.get("error")
        completed = None
        if error == "overloaded":
            # Re-raise-able typed backpressure: same exception type,
            # same depth/retry fields as the in-process path.
            error = EngineOverloaded(
                p.get("message", "engine overloaded"),
                queue_depth=p.get("queue_depth", 0),
                retry_after=p.get("retry_after", 0.0),
                tenant=p.get("tenant"))
        elif p.get("done") and error is None:
            completed = CompletedRequest(
                req_id=int(p["req"]),
                tokens=np.asarray(p["final_tokens"], np.int32),
                logprobs=np.asarray(p["logprobs"], np.float32),
                policy_logprobs=np.asarray(p["policy_logprobs"],
                                           np.float32))
        return StreamEvent(
            req_id=int(p["req"]),
            tokens=np.asarray(p.get("tokens", ()), np.int32),
            done=bool(p.get("done", False)),
            restarted=bool(p.get("restarted", False)),
            error=error, completed=completed)

    # -- failover --------------------------------------------------------
    def _failover(self) -> None:
        """Reconnect to a surviving replica and resume: rotate
        through the known edge set under seeded-jitter backoff (the
        per-client seed desynchronizes a thundering herd of orphaned
        clients — no resynchronized reconnect stampede), then
        re-submit every unsettled request with the ``resume`` flag.
        Raises :class:`GatewayClosed` when no replica survives.
        Serialized under ``_folock``: concurrent callers ride the
        first one's reconnect."""
        from orion_tpu.resilience import RetryPolicy

        with self._folock:
            if not self.closed.is_set():
                return  # another caller already failed us over
            reason = getattr(self, "_close_reason", "unknown")
            if self._user_closed or not self._failover_enabled:
                raise GatewayClosed(
                    f"gateway connection closed: {reason}")
            candidates = [p for p in self.edge_ports if p != self.port]
            if not candidates:
                raise GatewayClosed(
                    f"gateway connection closed: {reason} "
                    "(no surviving replica)")
            attempt = [0]

            def _dial_next():
                port = candidates[attempt[0] % len(candidates)]
                attempt[0] += 1
                self._connect(port)

            # closed stays set while we dial (submit() keeps failing
            # typed); _connect clears it only once a replica's HELLO
            # ack accepted us — before its recv thread starts, so the
            # thread's ``closed`` gate never sees the stale flag.
            policy = RetryPolicy(
                max_attempts=2 * len(candidates) + 2, base_delay=0.05,
                jitter=0.5, seed=zlib.crc32(self.name.encode()),
                retry_on=(ConnectionError, TimeoutError, OSError))
            try:
                policy.call(_dial_next)
            except (ConnectionError, TimeoutError, OSError) as e:
                self._events.put(self._CLOSED)
                raise GatewayClosed(
                    f"failover exhausted after {reason}: {e!r}") from e
            self.failovers += 1
            # Drop stale close sentinels; every REAL event queued
            # before the death is preserved in order (under _eqlock:
            # the new recv thread is already live and must not
            # interleave fresh events into the middle of the sweep).
            with self._eqlock:
                keep = []
                while True:
                    try:
                        ev = self._events.get_nowait()
                    except queue.Empty:
                        break
                    if ev is not self._CLOSED:
                        keep.append(ev)
                for ev in keep:
                    self._events.put(ev)
            with self._ilock:
                pending = sorted(self._inflight.items())
            for creq, payload in pending:
                self.chan.send_frame(FRAME_SUBMIT,
                                     {**payload, "req": int(creq),
                                      "resume": True})
            if obs.get_tracer().enabled:
                obs.instant("gw.client-failover", port=self.port,
                            resumed=len(pending), after=reason)

    # -- request surface -------------------------------------------------
    def submit(self, ids, budget: Optional[int] = None,
               priority: int = 0, deadline: Optional[int] = None,
               req_id: Optional[int] = None) -> int:
        """Fire-and-stream: returns the request id whose StreamEvents
        will arrive via :meth:`next_event`."""
        if self.closed.is_set():
            if not self._failover_enabled or self._user_closed:
                raise ConnectionError("gateway connection is closed")
            self._failover()
        if req_id is None:
            req_id = self._next_req
        self._next_req = max(self._next_req, int(req_id)) + 1
        payload = {"ids": np.asarray(ids, np.int32),
                   "budget": budget, "priority": int(priority),
                   "deadline": deadline}
        with self._ilock:
            self._inflight[int(req_id)] = payload
        try:
            self.chan.send_frame(FRAME_SUBMIT,
                                 {**payload, "req": int(req_id)})
        except (ConnectionError, TimeoutError, OSError):
            # The replica died under this very send.  The recv thread
            # flags the close momentarily; failover then re-submits
            # this request id from _inflight, so it is NOT lost.
            if not self._failover_enabled or self._user_closed \
                    or not self.closed.wait(timeout=5.0):
                with self._ilock:
                    self._inflight.pop(int(req_id), None)
                raise
            self._failover()
        return int(req_id)

    def submit_with_backoff(self, ids, budget: Optional[int] = None,
                            priority: int = 0,
                            deadline: Optional[int] = None,
                            policy=None,
                            event_timeout: float = 30.0,
                            sleep=time.sleep):
        """Submit with typed-backpressure retries: a shed
        (:class:`EngineOverloaded` riding the first StreamEvent) is
        retried under ``policy`` (a ``resilience.policy.RetryPolicy``;
        default 4 seeded-jitter attempts), sleeping at least the
        engine's ``retry_after`` hint each time.  Returns
        ``(req_id, first_event)`` for the attempt that was admitted;
        raises the final :class:`EngineOverloaded` once the budget is
        exhausted.  Events for OTHER in-flight requests arriving while
        we wait are re-queued, not dropped.

        Replica-aware (PR 20): a replica death mid-attempt is NOT a
        failed attempt — the typed :class:`GatewayClosed` is absorbed
        by failover (rotate to the next live replica under the same
        seeded-jitter discipline, idempotent re-submit of this very
        request id), the wait continues on the survivor, and the
        foreign events stashed before the death are still re-queued.
        Only an edge with no survivors surfaces ``GatewayClosed``."""
        from orion_tpu.resilience import RetryPolicy

        if policy is None:
            # Seeded per-cid jitter: simultaneous sheds across clients
            # desynchronize instead of re-stampeding in lockstep.
            policy = RetryPolicy(max_attempts=4, base_delay=0.05,
                                 jitter=0.5, seed=self.cid,
                                 retry_on=(EngineOverloaded,))
        hint = [0.0]   # retry_after from the most recent shed

        def _attempt():
            rid = self.submit(ids, budget=budget, priority=priority,
                              deadline=deadline)
            stash = []
            try:
                while True:
                    ev = self.next_event(timeout=event_timeout)
                    if ev is None:
                        raise TimeoutError(
                            f"no event for request {rid} within "
                            f"{event_timeout}s")
                    if ev.req_id != rid:
                        stash.append(ev)
                        continue
                    if isinstance(ev.error, EngineOverloaded):
                        hint[0] = float(ev.error.retry_after or 0.0)
                        raise ev.error
                    return rid, ev
            finally:
                if stash:
                    # Re-insert AHEAD of anything that arrived while
                    # we waited, preserving arrival order: a stashed
                    # RESTARTED marker re-queued behind later chunks
                    # would void the wrong prefix of its stream.
                    # ``_eqlock`` keeps the sweep atomic against the
                    # recv loop; duck-typed clients that borrow this
                    # method (pool backoff shims) have no recv thread
                    # and no lock — a throwaway lock keeps the same
                    # shape.
                    with getattr(self, "_eqlock", None) or \
                            threading.Lock():
                        later = []
                        while True:
                            try:
                                later.append(self._events.get_nowait())
                            except queue.Empty:
                                break
                        for s in stash + later:
                            self._events.put(s)

        def _sleep(delay: float) -> None:
            # The policy's jittered schedule is the floor; the
            # engine's own drain estimate wins when longer.
            sleep(max(float(delay), hint[0]))

        return policy.call(_attempt, sleep=_sleep)

    def cancel(self, req_id: int) -> None:
        with self._ilock:
            self._inflight.pop(int(req_id), None)
        self.chan.send_frame(FRAME_CANCEL, {"req": int(req_id)})

    def next_event(self, timeout: Optional[float] = None
                   ) -> Optional[StreamEvent]:
        """The next StreamEvent from any in-flight request, or None on
        timeout.  Against a replicated edge a dead connection is
        failed over TRANSPARENTLY (reconnect + idempotent re-submit;
        the stream continues, prior partials voided by the RESTARTED
        marker).  Raises :class:`GatewayClosed` (a ConnectionError)
        once the channel is closed with no surviving replica AND the
        buffered events are drained — including from a
        ``timeout=None`` block: the recv loop's closing sentinel
        wakes the wait, so a gateway drain (server preemption
        GOODBYE) surfaces immediately as the typed error instead of
        hanging."""
        try:
            ev = self._events.get(timeout=timeout)
        except queue.Empty:
            if self.closed.is_set():
                raise GatewayClosed(
                    "gateway connection closed") from None
            return None
        if ev is self._CLOSED:
            if self._failover_enabled and not self._user_closed \
                    and any(p != self.port for p in self.edge_ports):
                self._failover()  # raises GatewayClosed if exhausted
                return self.next_event(timeout=timeout)
            # Keep the sentinel visible to any other waiter, then
            # surface the typed close.
            self._events.put(self._CLOSED)
            raise GatewayClosed(
                "gateway connection closed: "
                f"{getattr(self, '_close_reason', 'unknown')}")
        return ev

    def close(self) -> None:
        self._user_closed = True
        if not self.closed.is_set():
            try:
                self.chan.send_frame(FRAME_GOODBYE, {"reason": "done"})
            except (ConnectionError, TimeoutError, OSError):
                pass
        self.closed.set()
        self._close_reason = getattr(self, "_close_reason",
                                     "closed by client")
        self._events.put(self._CLOSED)
        try:
            self.chan.close()
        except OSError:
            pass
