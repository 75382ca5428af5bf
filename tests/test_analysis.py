"""orion_tpu.analysis: rule fixtures (one positive + one negative per
rule — multi-file dict fixtures exercise the PROJECT phase),
suppression, the CLI exit codes + CI formats (json/sarif/baseline),
the result cache, the runtime guards — and the self-gate: both phases
over the shipped tree must report ZERO unsuppressed findings, so every
future PR keeps the repo lint-clean.

Named test_analysis.py deliberately: it sorts early in tier-1 and the
whole file is AST-only except the two runtime-guard tests, so the gate
costs seconds.
"""

import json
import os
import logging
import subprocess
import sys
import textwrap
import warnings

import pytest

from orion_tpu.analysis import (RULES, analyze_paths, analyze_source,
                                analyze_sources, format_findings)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LINT_PATHS = ("orion_tpu", "tests", "scripts", "__graft_entry__.py")


def ids_of(findings):
    return {f.rule_id for f in findings}


def run_on(snippet: str, path: str = "x.py"):
    return analyze_source(textwrap.dedent(snippet), path)


def run_on_files(files: dict):
    """Run both phases over an in-memory multi-module project — the
    cross-file (project-rule) analogue of :func:`run_on`."""
    return analyze_sources([(p, textwrap.dedent(s))
                            for p, s in files.items()])


# ---------------------------------------------------------------------------
# per-rule fixtures: (rule-id, fires, clean, path)
# ---------------------------------------------------------------------------

FIXTURES = [
    (
        "compat-import",
        """
        from jax import shard_map
        """,
        """
        from orion_tpu.utils.platform import axis_size, shard_map
        """,
        "x.py",
    ),
    (
        "compat-import",
        """
        from jax import lax

        def f(x):
            return lax.axis_size("seq")
        """,
        """
        from orion_tpu.utils.platform import axis_size

        def f(x):
            return axis_size("seq")
        """,
        "x.py",
    ),
    (
        "host-sync-in-jit",
        """
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()
        """,
        """
        import jax

        @jax.jit
        def f(x):
            return x.sum()

        def fetch(x):
            return f(x).item()  # host side: fine
        """,
        "x.py",
    ),
    (
        "host-sync-in-jit",
        """
        import functools
        import jax
        import jax.numpy as jnp

        @functools.partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            return float(jnp.mean(x)) * n
        """,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, scale: float):
            return jnp.mean(x) * float(scale)
        """,
        "x.py",
    ),
    (
        "host-sync-in-jit",
        """
        import jax
        import numpy as np

        def outer(x):
            def body(c, _):
                return np.asarray(c), None
            return jax.lax.scan(body, x, None, length=3)
        """,
        """
        import jax
        import jax.numpy as jnp

        def outer(x):
            def body(c, _):
                return jnp.asarray(c), None
            return jax.lax.scan(body, x, None, length=3)
        """,
        "x.py",
    ),
    (
        "host-sync-in-jit",
        """
        import jax

        def outer(x, n):
            def body(i, c):
                return c + c.sum().item()
            return jax.lax.fori_loop(0, n, body, x)
        """,
        """
        import jax

        def scan_user(x):
            def body(c, _):
                return c * 2, None
            return jax.lax.scan(body, x, None, length=3)

        def host_helper(results):
            def body(r):
                return r.sum().item()  # host side, own scope's 'body'
            return [body(r) for r in results]
        """,
        "x.py",
    ),
    (
        "impure-in-jit",
        """
        import jax

        def outer(x):
            def cond(c):
                return c.sum() < 10

            def body(c):
                print("trace me not", c)
                return c + 1
            return jax.lax.while_loop(cond, body, x)
        """,
        """
        import jax

        def outer(x):
            def cond(c):
                return c.sum() < 10

            def body(c):
                return c + 1
            out = jax.lax.while_loop(cond, body, x)
            print("host side:", out)
            return out
        """,
        "x.py",
    ),
    (
        "prng-reuse",
        """
        import jax

        def sample(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
        """,
        """
        import jax

        def sample(key):
            key, sub = jax.random.split(key)
            a = jax.random.normal(sub, (2,))
            key, sub = jax.random.split(key)
            b = jax.random.uniform(sub, (2,))
            return a + b
        """,
        "x.py",
    ),
    (
        "prng-reuse",
        """
        import jax

        def loop(rng, n):
            out = []
            for _ in range(n):
                out.append(jax.random.normal(rng, (2,)))
            return out
        """,
        """
        import jax

        def loop(rng, n):
            out = []
            for i in range(n):
                sub = jax.random.fold_in(rng, i)
                out.append(jax.random.normal(sub, (2,)))
            return out
        """,
        "x.py",
    ),
    (
        "impure-in-jit",
        """
        import jax

        @jax.jit
        def f(x):
            print("value:", x)
            return x
        """,
        """
        import jax

        @jax.jit
        def f(x):
            jax.debug.print("value: {}", x)
            return x
        """,
        "x.py",
    ),
    (
        "impure-in-jit",
        """
        import time
        import jax

        @jax.jit
        def f(x):
            return x * time.time()
        """,
        """
        import time
        import jax

        @jax.jit
        def f(x):
            return x * 2

        def bench(x):
            t0 = time.time()
            return f(x), time.time() - t0
        """,
        "x.py",
    ),
    (
        "traced-branch",
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.any(x > 0):
                return x
            return -x
        """,
        """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, *, causal: bool = True):
            if causal:
                x = jnp.tril(x)
            return jnp.where(jnp.any(x > 0), x, -x)
        """,
        "x.py",
    ),
    (
        "mutable-default",
        """
        def collect(x, acc=[]):
            acc.append(x)
            return acc
        """,
        """
        def collect(x, acc=None):
            acc = [] if acc is None else acc
            acc.append(x)
            return acc
        """,
        "x.py",
    ),
    (
        "mutable-default",
        """
        import dataclasses

        @dataclasses.dataclass
        class Cfg:
            layers: object = []
        """,
        """
        import dataclasses

        @dataclasses.dataclass
        class Cfg:
            layers: object = dataclasses.field(default_factory=list)
        """,
        "x.py",
    ),
    (
        "donated-reuse",
        """
        import jax

        def run(step, state, batch):
            step2 = jax.jit(step, donate_argnums=(0,))
            out = step2(state, batch)
            return out, state
        """,
        """
        import jax

        def run(step, state, batch):
            step2 = jax.jit(step, donate_argnums=(0,))
            state = step2(state, batch)
            return state
        """,
        "x.py",
    ),
    (
        "bench-no-block",
        """
        import time

        def bench(f, x):
            t0 = time.perf_counter()
            y = f(x)
            return y, time.perf_counter() - t0
        """,
        """
        import time
        import jax

        def bench(f, x):
            t0 = time.perf_counter()
            y = jax.block_until_ready(f(x))
            return y, time.perf_counter() - t0
        """,
        "bench_fake.py",
    ),
    (
        "bench-no-block",
        """
        import time

        def bench(f, x):
            t0 = time.time()
            for _ in range(8):
                y = f(x)
            return time.time() - t0
        """,
        """
        import time
        import numpy as np

        def bench(f, x):
            t0 = time.time()
            for _ in range(8):
                y = np.asarray(f(x))
            return time.time() - t0
        """,
        "bench_fake.py",
    ),
    (
        "unsupervised-thread",
        """
        import threading

        def spawn(fn):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            return t
        """,
        """
        import threading

        def spawn(fn, watchdog):
            hb = watchdog.register("worker", timeout=30.0)
            t = threading.Thread(target=fn, args=(hb,), daemon=True)
            t.start()
            return t
        """,
        "orion_tpu/fake_worker.py",
    ),
    (
        "unsupervised-thread",
        """
        from threading import Thread

        def spawn(fn):
            return Thread(target=fn)
        """,
        """
        from threading import Thread

        from orion_tpu.resilience import Watchdog

        def spawn(fn):
            Watchdog().register("worker", timeout=5.0)
            return Thread(target=fn)
        """,
        "orion_tpu/fake_worker2.py",
    ),
    (
        "naked-timer",
        """
        import time

        def measure(f):
            t0 = time.monotonic()
            f()
            return time.monotonic() - t0
        """,
        """
        from orion_tpu.obs import timed

        def measure(f):
            with timed("measure") as sp:
                f()
            return sp.duration
        """,
        "orion_tpu/fake_timing.py",
    ),
    (
        "naked-timer",
        """
        import time

        def step_rate(step):
            t0 = time.time()
            step()
            dt = time.time() - t0
            return 1.0 / dt
        """,
        """
        import time

        def wait_until(cond, timeout):
            deadline = time.monotonic() + timeout
            while not cond():
                if time.monotonic() - deadline > 0:
                    raise TimeoutError("deadline")
        """,
        "orion_tpu/fake_timing.py",
    ),
    (
        "raw-socket",
        """
        import socket

        def dial(host, port):
            return socket.create_connection((host, port))
        """,
        """
        from orion_tpu.orchestration.remote import PyTreeChannel

        def dial(port):
            return PyTreeChannel.connect(port)
        """,
        "orion_tpu/fake_io.py",
    ),
    (
        "raw-socket",
        """
        import socket

        def serve():
            s = socket.socket()
            s.bind(("localhost", 0))
            return s
        """,
        """
        from orion_tpu.orchestration.remote import WorkerPool

        def serve():
            return WorkerPool(0)
        """,
        "orion_tpu/fake_io.py",
    ),
    (
        # the seeded race: the PR 6 TRAJ-discard shape — a recv thread
        # reads `alive` bare while consume/shutdown guard it
        "lock-discipline",
        """
        import queue
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.alive = True
                self.inbox = queue.Queue()
                self.discarded = 0
                self._t = threading.Thread(target=self._recv_loop)
                self._t.start()

            def consume(self):
                with self._lock:
                    if self.alive:
                        return self.inbox.get_nowait()
                    return None

            def shutdown(self):
                with self._lock:
                    self.alive = False
                    self.discarded += 1

            def _recv_loop(self):
                while self.alive:
                    self.inbox.put(1)
        """,
        """
        import queue
        import threading

        class Pool:
            def __init__(self):
                self._lock = threading.Lock()
                self.alive = True
                self.inbox = queue.Queue()
                self.discarded = 0
                self._t = threading.Thread(target=self._recv_loop)
                self._t.start()

            def consume(self):
                with self._lock:
                    if self.alive:
                        return self.inbox.get_nowait()
                    return None

            def shutdown(self):
                with self._lock:
                    self.alive = False
                    self.discarded += 1

            def _recv_loop(self):
                while True:
                    with self._lock:
                        if not self.alive:
                            return
                        self.inbox.put(1)
        """,
        "pool.py",
    ),
    (
        # dispatch gap: FRAME_C silently dropped, no raising else
        "frame-exhaustive",
        """
        FRAME_A = 0
        FRAME_B = 1
        FRAME_C = 2

        def dispatch(kind, payload):
            if kind == FRAME_A:
                return payload
            elif kind == FRAME_B:
                return None
        """,
        """
        FRAME_A = 0
        FRAME_B = 1
        FRAME_C = 2

        def dispatch(kind, payload):
            if kind == FRAME_A:
                return payload
            elif kind == FRAME_B:
                return None
            else:
                raise ValueError(f"unexpected frame {kind}")
        """,
        "wire.py",
    ),
    (
        # ISSUE 12: the gateway's SUBMIT/STREAM/CANCEL family is the
        # SECOND frame family in the tree — the per-module scoping
        # must keep the fully-handled pool chain clean while the
        # gateway chain silently dropping one of ITS OWN frames (plus
        # an imported HELLO) still fires.
        "frame-exhaustive",
        {
            "wire.py": """
            FRAME_HELLO = 1
            FRAME_GOODBYE = 5

            def pool_dispatch(kind, payload):
                if kind == FRAME_HELLO:
                    return payload
                elif kind == FRAME_GOODBYE:
                    return None
                else:
                    raise ValueError(f"unexpected frame {kind}")
            """,
            "gateway.py": """
            from wire import FRAME_HELLO

            FRAME_SUBMIT = 16
            FRAME_STREAM = 17
            FRAME_CANCEL = 18

            def gw_dispatch(kind, payload):
                if kind == FRAME_SUBMIT:
                    return ("submit", payload)
                elif kind == FRAME_STREAM:
                    return ("stream", payload)
                # CANCEL (and the imported HELLO) silently dropped
            """,
        },
        {
            "wire.py": """
            FRAME_HELLO = 1
            FRAME_GOODBYE = 5

            def pool_dispatch(kind, payload):
                if kind == FRAME_HELLO:
                    return payload
                elif kind == FRAME_GOODBYE:
                    return None
                else:
                    raise ValueError(f"unexpected frame {kind}")
            """,
            "gateway.py": """
            from wire import FRAME_HELLO

            FRAME_SUBMIT = 16
            FRAME_STREAM = 17
            FRAME_CANCEL = 18

            def gw_dispatch(kind, payload):
                if kind == FRAME_HELLO:
                    return ("hello", payload)
                elif kind == FRAME_SUBMIT:
                    return ("submit", payload)
                elif kind == FRAME_STREAM:
                    return ("stream", payload)
                else:
                    raise ValueError(f"unexpected frame {kind}")
            """,
        },
        None,
    ),
    (
        # ISSUE 17: the prefill-tier KV handoff family (OFFER/PAGES/
        # ACK) is the THIRD frame family — a tier module importing the
        # shared HELLO/GOODBYE and silently dropping one of its own KV
        # frames must fire, while the fully-handled worker-side chain
        # (subset + loud else) stays clean.
        "frame-exhaustive",
        {
            "wire_kv.py": """
            FRAME_HELLO = 1
            FRAME_GOODBYE = 5
            """,
            "prefill.py": """
            from wire_kv import FRAME_GOODBYE, FRAME_HELLO

            FRAME_KV_OFFER = 32
            FRAME_KV_PAGES = 33
            FRAME_KV_ACK = 34

            def worker_dispatch(kind, payload):
                if kind == FRAME_KV_OFFER:
                    return ("prefill", payload)
                elif kind == FRAME_GOODBYE:
                    return None
                # KV_ACK (telemetry) and a stray HELLO silently eaten
            """,
        },
        {
            "wire_kv.py": """
            FRAME_HELLO = 1
            FRAME_GOODBYE = 5
            """,
            "prefill.py": """
            from wire_kv import FRAME_GOODBYE, FRAME_HELLO

            FRAME_KV_OFFER = 32
            FRAME_KV_PAGES = 33
            FRAME_KV_ACK = 34

            def worker_dispatch(kind, payload):
                if kind == FRAME_KV_OFFER:
                    return ("prefill", payload)
                elif kind == FRAME_KV_ACK:
                    return ("ack", payload)
                elif kind == FRAME_GOODBYE:
                    return None
                else:
                    raise ValueError(f"unexpected frame {kind}")
            """,
        },
        None,
    ),
    (
        # ISSUE 18: the v7 WEIGHTS-commit handshake adds WEIGHTS_ACK
        # to the pool family — a learner recv chain that handles the
        # push frames but silently eats the ACK (so staged pushes
        # never confirm and every rollout would hang at the commit
        # barrier) must fire; the same chain with the ACK branch and
        # a loud else stays clean.
        "frame-exhaustive",
        """
        FRAME_HEARTBEAT = 2
        FRAME_WEIGHTS = 4
        FRAME_WEIGHTS_ACK = 7

        def learner_dispatch(kind, payload):
            if kind == FRAME_HEARTBEAT:
                return None
            elif kind == FRAME_WEIGHTS:
                return ("push", payload)
            # WEIGHTS_ACK silently dropped: staged commit never lands
        """,
        """
        FRAME_HEARTBEAT = 2
        FRAME_WEIGHTS = 4
        FRAME_WEIGHTS_ACK = 7

        def learner_dispatch(kind, payload):
            if kind == FRAME_HEARTBEAT:
                return None
            elif kind == FRAME_WEIGHTS:
                return ("push", payload)
            elif kind == FRAME_WEIGHTS_ACK:
                return ("acked", payload)
            else:
                raise ValueError(f"unexpected frame {kind}")
        """,
        "wire_ack.py",
    ),
    (
        # header format drifted from the registered PROTOCOL_VERSION
        # entry (the PR 9 v3-to-v4 rule, structurally checked)
        "frame-exhaustive",
        """
        import struct

        PROTOCOL_VERSION = 2
        _HEADER = struct.Struct(">4sHB")
        _HEADER_HISTORY = {1: ">4sH", 2: ">4sHQ"}
        """,
        """
        import struct

        PROTOCOL_VERSION = 2
        _HEADER = struct.Struct(">4sHB")
        _HEADER_HISTORY = {1: ">4sH", 2: ">4sHB"}
        """,
        "wire2.py",
    ),
    (
        # orphaned knob: a field nothing outside the config module reads
        "config-drift",
        {
            "myconfig.py": """
            import dataclasses

            @dataclasses.dataclass
            class ServeConfig:
                port: int = 0
                orphan_knob: int = 2
            """,
            "server.py": """
            def serve(cfg):
                return cfg.port
            """,
        },
        {
            "myconfig.py": """
            import dataclasses

            @dataclasses.dataclass
            class ServeConfig:
                port: int = 0
                orphan_knob: int = 2
            """,
            "server.py": """
            def serve(cfg):
                return cfg.port + cfg.orphan_knob
            """,
        },
        None,
    ),
    (
        # phantom read: a cfg.* access naming a field no config defines
        "config-drift",
        {
            "myconfig.py": """
            import dataclasses

            @dataclasses.dataclass
            class ServeConfig:
                port: int = 0
            """,
            "server.py": """
            def serve(cfg):
                return cfg.prot
            """,
        },
        {
            "myconfig.py": """
            import dataclasses

            @dataclasses.dataclass
            class ServeConfig:
                port: int = 0
            """,
            "server.py": """
            def serve(cfg):
                return cfg.port
            """,
        },
        None,
    ),
    (
        # ISSUE 18: the rollout_update knob family — a blue/green
        # coordinator that stops reading one of its ladder knobs
        # (drain deadline silently hardcoded) is drift; reading every
        # knob outside the config module is clean.
        "config-drift",
        {
            "rollcfg.py": """
            import dataclasses

            @dataclasses.dataclass
            class RolloutUpdateConfig:
                canary_prompts: int = 2
                drain_deadline_ticks: int = 200
            """,
            "coordinator.py": """
            def advance(cfg):
                return cfg.canary_prompts
            """,
        },
        {
            "rollcfg.py": """
            import dataclasses

            @dataclasses.dataclass
            class RolloutUpdateConfig:
                canary_prompts: int = 2
                drain_deadline_ticks: int = 200
            """,
            "coordinator.py": """
            def advance(cfg):
                return cfg.canary_prompts + cfg.drain_deadline_ticks
            """,
        },
        None,
    ),
    (
        "unused-suppression",
        """
        X = 1  # orion: ignore[prng-reuse] stale justification
        """,
        """
        import jax

        @jax.jit
        def f(x):
            return x.sum().item()  # orion: ignore[host-sync-in-jit] dbg
        """,
        "x.py",
    ),
    (
        # ISSUE 19 phase 3: the classic two-class lock inversion — the
        # gateway routes under ITS lock into the pool (which takes the
        # pool lock), while the pool's death path calls back into the
        # gateway under the POOL lock.  The negative releases the pool
        # lock before the callback: consistent global order, no cycle.
        "lock-order",
        {
            "orion_tpu/orchestration/lo_pool.py": """
            import threading

            class Pool:
                def __init__(self, gw):
                    self._lock = threading.Lock()
                    self.gw = gw
                    self.dead = []

                def mark_dead(self, name):
                    with self._lock:
                        self.dead.append(name)
                        self.gw.drop(name)
            """,
            "orion_tpu/orchestration/lo_gw.py": """
            import threading

            class Gateway:
                def __init__(self, pool):
                    self._lock = threading.Lock()
                    self.pool = pool
                    self.routes = {}

                def route(self, name):
                    with self._lock:
                        self.pool.mark_dead(name)

                def drop(self, name):
                    with self._lock:
                        self.routes.pop(name, None)
            """,
        },
        {
            "orion_tpu/orchestration/lo_pool.py": """
            import threading

            class Pool:
                def __init__(self, gw):
                    self._lock = threading.Lock()
                    self.gw = gw
                    self.dead = []

                def mark_dead(self, name):
                    with self._lock:
                        self.dead.append(name)
                    self.gw.drop(name)
            """,
            "orion_tpu/orchestration/lo_gw.py": """
            import threading

            class Gateway:
                def __init__(self, pool):
                    self._lock = threading.Lock()
                    self.pool = pool
                    self.routes = {}

                def route(self, name):
                    with self._lock:
                        self.pool.mark_dead(name)

                def drop(self, name):
                    with self._lock:
                        self.routes.pop(name, None)
            """,
        },
        None,
    ),
    (
        # ISSUE 19 phase 3: an unbounded sleep THREE hops below the
        # gateway pump — only the interprocedural walk sees it.  The
        # negative waits on an Event WITH a timeout (bounded waits are
        # the pump-safe idiom).
        "blocking-in-pump",
        {
            "orion_tpu/orchestration/bp_gw.py": """
            import time

            class Gateway:
                def step(self):
                    self._drain()

                def _drain(self):
                    self._wait_ready()

                def _wait_ready(self):
                    time.sleep(0.5)
            """,
        },
        {
            "orion_tpu/orchestration/bp_gw.py": """
            import threading

            class Gateway:
                def __init__(self):
                    self.ready = threading.Event()

                def step(self):
                    self._drain()

                def _drain(self):
                    self._wait_ready()

                def _wait_ready(self):
                    self.ready.wait(0.5)
            """,
        },
        None,
    ),
    (
        # ISSUE 19 phase 3: both drift directions at once — a consumer
        # subscripts a key the producer never emits (typo'd read) AND
        # a produced counter nothing anywhere reads or mentions.
        "telemetry-drift",
        {
            "orion_tpu/obs/td_prod.py": """
            class Telemetry:
                def server_stats(self):
                    return {"requests_finished": 1.0, "queue_depth": 2.0}
            """,
            "orion_tpu/rollout/td_cons.py": """
            def watch(t):
                stats = t.server_stats()
                return stats["requests_finishedd"], stats["queue_depth"]
            """,
        },
        {
            "orion_tpu/obs/td_prod.py": """
            class Telemetry:
                def server_stats(self):
                    return {"requests_finished": 1.0, "queue_depth": 2.0}
            """,
            "orion_tpu/rollout/td_cons.py": """
            def watch(t):
                stats = t.server_stats()
                return stats["requests_finished"], stats["queue_depth"]
            """,
        },
        None,
    ),
    (
        # ISSUE 19 phase 3: a registered fault point no library call
        # site ever fires — untested chaos surface.  The negative
        # fires both points and exercises both from a test plan spec.
        "fault-coverage",
        {
            "orion_tpu/resilience/fc_inject.py": """
            FAULT_POINTS = frozenset({"save.blob", "load.blob"})
            """,
            "orion_tpu/utils/fc_ck.py": """
            def save():
                fault_point("save.blob")
            """,
            "tests/test_fc_ck.py": """
            def test_save_fault():
                plan = {"save.blob": {"at": 1}}
                assert plan
            """,
        },
        {
            "orion_tpu/resilience/fc_inject.py": """
            FAULT_POINTS = frozenset({"save.blob", "load.blob"})
            """,
            "orion_tpu/utils/fc_ck.py": """
            def save():
                fault_point("save.blob")

            def load():
                fault_point("load.blob")
            """,
            "tests/test_fc_ck.py": """
            def test_fault_plans():
                plans = [{"save.blob": {"at": 1}},
                         {"load.blob": {"at": 2}}]
                assert plans
            """,
        },
        None,
    ),
]


@pytest.mark.parametrize(
    "rule_id,pos,neg,path",
    FIXTURES,
    ids=[f"{r}-{i}" for i, (r, *_rest) in enumerate(FIXTURES)])
def test_rule_fixtures(rule_id, pos, neg, path):
    run = run_on_files if isinstance(pos, dict) else \
        (lambda s: run_on(s, path))
    hits = run(pos)
    assert rule_id in ids_of(hits), \
        f"positive fixture did not fire {rule_id}"
    assert all(f.hint for f in hits if f.rule_id == rule_id), \
        "every finding carries a fix hint"
    assert rule_id not in ids_of(run(neg)), \
        f"negative fixture wrongly fired {rule_id}"


def test_every_rule_has_fixture_coverage():
    covered = {r for r, *_ in FIXTURES}
    assert covered == {r.id for r in RULES}, \
        "each registered rule needs a positive+negative fixture here"
    assert len(RULES) >= 19
    kinds = {r.id: getattr(r, "kind", "file") for r in RULES}
    assert {k for k, v in kinds.items() if v == "project"} == \
        {"lock-discipline", "frame-exhaustive", "config-drift",
         "lock-order", "blocking-in-pump", "telemetry-drift",
         "fault-coverage"}


def test_naked_timer_exempts_obs_and_tests():
    """orion_tpu/obs IS the timing layer and tests time their own
    scaffolding freely — the same delta fires everywhere else."""
    snippet = """
    import time

    def measure(f):
        t0 = time.perf_counter()
        f()
        return time.perf_counter() - t0
    """
    assert "naked-timer" in ids_of(run_on(snippet, "orion_tpu/rollout/x.py"))
    assert "naked-timer" not in ids_of(
        run_on(snippet, "orion_tpu/obs/trace.py"))
    assert "naked-timer" not in ids_of(run_on(snippet, "tests/test_x.py"))


def test_naked_timer_deadline_arithmetic_is_clean():
    """`deadline = now + timeout` and `remaining = deadline - now` are
    deadline bookkeeping, not timing measurements — the rule must not
    fire on the retry/connect-backoff idiom."""
    snippet = """
    import time

    def connect(timeout):
        deadline = time.monotonic() + timeout
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError
    """
    assert "naked-timer" not in ids_of(
        run_on(snippet, "orion_tpu/fake_io.py"))


def test_raw_socket_allowed_only_in_remote_py():
    """The one module allowed to touch sockets IS the hardened
    channel — the same snippet fires everywhere else."""
    snippet = """
    import socket

    def dial(port):
        return socket.create_connection(("localhost", port))
    """
    assert "raw-socket" in ids_of(run_on(snippet, "orion_tpu/fake.py"))
    assert "raw-socket" not in ids_of(
        run_on(snippet, "orion_tpu/orchestration/remote.py"))


# ---------------------------------------------------------------------------
# suppression + report format
# ---------------------------------------------------------------------------

SUPPRESSIBLE = """
import jax

@jax.jit
def f(x):
    return x.sum().item()  # orion: ignore[host-sync-in-jit] eager debug
"""


def test_suppression_comment_silences_the_line():
    assert run_on(SUPPRESSIBLE) == []


def test_suppression_requires_matching_rule_id():
    wrong = SUPPRESSIBLE.replace("host-sync-in-jit", "prng-reuse")
    assert "host-sync-in-jit" in ids_of(run_on(wrong))


def test_bare_suppression_silences_every_rule():
    bare = SUPPRESSIBLE.replace("ignore[host-sync-in-jit] eager debug",
                                "ignore")
    assert run_on(bare) == []


def test_report_format_has_file_line_and_hint():
    findings = run_on(SUPPRESSIBLE.replace("  # orion: ignore"
                                           "[host-sync-in-jit] eager "
                                           "debug", ""), "mod.py")
    text = format_findings(findings)
    assert "mod.py:6:" in text
    assert "[host-sync-in-jit]" in text
    assert "hint:" in text


def test_syntax_error_reports_instead_of_crashing():
    bad = run_on("def f(:\n")
    assert [f.rule_id for f in bad] == ["syntax-error"]


# ---------------------------------------------------------------------------
# CLI exit codes
# ---------------------------------------------------------------------------


def _run_cli(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # --no-cache: tests must never write tmp-path entries into the
    # developer's live lint cache under ~/.cache
    return subprocess.run(
        [sys.executable, "-m", "orion_tpu.analysis", "--no-cache",
         *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)


def test_cli_exit_codes(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    clean = tmp_path / "clean.py"
    clean.write_text("from orion_tpu.utils.platform import shard_map\n")

    r = _run_cli(str(dirty))
    assert r.returncode == 1, r.stderr
    assert "dirty.py:1:" in r.stdout and "compat-import" in r.stdout

    r = _run_cli(str(clean))
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout == ""


def test_cli_missing_path_errors(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    assert main([str(tmp_path / "renamed_away.py")]) == 2
    assert "renamed_away.py" in capsys.readouterr().err


def test_cli_rule_filter_and_listing(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    assert main(["--no-cache", "--rule", "prng-reuse",
                 str(dirty)]) == 0
    assert main(["--no-cache", str(dirty)]) == 1
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rl in RULES:
        assert rl.id in out


# ---------------------------------------------------------------------------
# the self-gate: the shipped tree stays clean
# ---------------------------------------------------------------------------


def test_repo_tree_is_clean_full_gate():
    """THE self-gate: both phases over the exact scripts/lint.sh path
    set in ONE invocation (the project rules need every cross-file
    reader in view) — zero unsuppressed findings, all SEVEN project
    rules ENABLED (full registry, no --rule filter, no baseline).
    The run's SARIF report lands in the log dir either way, so CI has
    the machine-readable artifact even (especially) on a red gate."""
    import tempfile

    from orion_tpu.analysis.report import format_sarif

    findings = analyze_paths([os.path.join(REPO, p)
                              for p in LINT_PATHS])
    log_dir = os.environ.get(
        "ORION_ANALYSIS_LOG_DIR",
        os.path.join(tempfile.gettempdir(), "orion-analysis-logs"))
    try:
        os.makedirs(log_dir, exist_ok=True)
        with open(os.path.join(log_dir, "lint.sarif"), "w",
                  encoding="utf-8") as fh:
            fh.write(format_sarif(findings, rules=RULES))
    except OSError:
        pass  # read-only CI scratch: the artifact is best-effort
    assert findings == [], "\n" + format_findings(findings)


def test_gate_catches_a_seeded_violation(tmp_path):
    scratch = tmp_path / "scratch.py"
    scratch.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def step(x):
            return x.sum().item()
    """))
    findings = analyze_paths([str(tmp_path)])
    assert any(f.rule_id == "host-sync-in-jit" and f.line == 6
               for f in findings), format_findings(findings)


# ---------------------------------------------------------------------------
# runtime guards
# ---------------------------------------------------------------------------


def test_recompile_sentinel_counts_and_warns():
    import jax
    import jax.numpy as jnp

    from orion_tpu.analysis.runtime_guards import RecompileSentinel

    sentinel = RecompileSentinel(budget=1).install()
    try:
        @jax.jit
        def poly_fn_for_sentinel(x):
            return x * 2 + 1

        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for n in (3, 4, 5):  # three shapes => three compiles
                poly_fn_for_sentinel(jnp.ones((n,)))
        assert sentinel.counts.get("poly_fn_for_sentinel", 0) >= 2
        assert sentinel.total_compiles >= 2
        msgs = [str(w.message) for w in caught
                if issubclass(w.category, RuntimeWarning)]
        assert any("recompile sentinel" in m
                   and "poly_fn_for_sentinel" in m for m in msgs), msgs
    finally:
        sentinel.uninstall()
    assert not jax.config.jax_log_compiles


def test_stacked_sentinels_leave_jax_as_found():
    """Two live sentinels share the compile watch's ONE pair of
    listeners: the first uninstall leaves the second counting, the
    last leaves ``jax.monitoring`` as found; ``jax_log_compiles`` and
    the ``jax`` logger are never touched (ISSUE 51)."""
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    from orion_tpu.analysis.runtime_guards import RecompileSentinel

    def listeners():
        return (list(monitoring.get_event_duration_listeners()),
                list(monitoring.get_event_listeners()))

    orig = bool(jax.config.jax_log_compiles)
    handlers = list(logging.getLogger("jax").handlers)
    found = listeners()
    a = RecompileSentinel(budget=3).install()
    grown = listeners()
    b = RecompileSentinel(budget=3).install()
    assert listeners() == grown     # one pair, however many holds
    a.uninstall()
    assert listeners() == grown and b.installed  # b still live
    jax.jit(lambda x: x - 3, inline=False)(jnp.ones((7,)))
    assert b.total_compiles >= 1 and not a.installed
    b.uninstall()
    b.uninstall()   # idempotent
    assert listeners() == found
    assert bool(jax.config.jax_log_compiles) == orig
    assert logging.getLogger("jax").handlers == handlers


def test_trainer_close_uninstalls_sentinel():
    from orion_tpu.config import TrainConfig
    from orion_tpu.trainers.base import BaseTrainer

    class _Shell:
        close = BaseTrainer.close

    shell = _Shell()
    from orion_tpu.analysis.runtime_guards import install_from_config
    shell._recompile_sentinel = install_from_config(
        TrainConfig(recompile_budget=2))
    sentinel = shell._recompile_sentinel
    assert sentinel.installed
    shell.close()
    assert not sentinel.installed
    assert shell._recompile_sentinel is None
    shell.close()  # idempotent


def test_guard_scope_wires_transfer_guard():
    import jax

    from orion_tpu.analysis.runtime_guards import guard_scope

    before = jax.config.jax_transfer_guard
    with guard_scope("log"):
        assert jax.config.jax_transfer_guard == "log"
    assert jax.config.jax_transfer_guard == before
    with guard_scope(None):  # no-op path
        assert jax.config.jax_transfer_guard == before


def test_install_from_config_respects_budget():
    from orion_tpu.analysis.runtime_guards import install_from_config
    from orion_tpu.config import TrainConfig

    assert install_from_config(TrainConfig()) is None
    sentinel = install_from_config(TrainConfig(recompile_budget=5))
    try:
        assert sentinel is not None and sentinel.budget == 5
    finally:
        sentinel.uninstall()


# ---------------------------------------------------------------------------
# project phase: cross-file behavior, suppression, wire-history mirror
# ---------------------------------------------------------------------------


def test_project_rule_suppression_and_unused_judgment():
    """A project-rule finding obeys the same per-line suppression as a
    per-file finding — and the unused-suppression sweep counts it as
    USED (a stale-vs-live judgment needs the project phase's verdict,
    which is why the sweep runs last)."""
    src = """
    import queue
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self.alive = True
            self.inbox = queue.Queue()
            self._t = threading.Thread(target=self._recv_loop)

        def consume(self):
            with self._lock:
                if self.alive:
                    return self.inbox.get_nowait()
                return None

        def shutdown(self):
            with self._lock:
                self.alive = False

        def _recv_loop(self):
            while self.alive:  # orion: ignore[lock-discipline] bool read is atomic here, latest-wins is fine
                self.inbox.put(1)
    """
    got = ids_of(run_on(src, "pool.py"))
    assert "lock-discipline" not in got
    assert "unused-suppression" not in got


def test_config_drift_nested_chain_and_getattr():
    """The TrainConfig shape: `cfg.rollout.<field>` resolves through
    the sub-config's annotation, and a 2-arg getattr with a string
    literal is checked too (3-arg defaults are deliberately exempt)."""
    files = {
        "myconfig.py": """
        import dataclasses

        @dataclasses.dataclass
        class RollConfig:
            page_watermark: int = -1

        @dataclasses.dataclass
        class TopConfig:
            rollout: RollConfig = dataclasses.field(
                default_factory=RollConfig)
        """,
        "engine.py": """
        def build(cfg):
            a = cfg.rollout.page_watermark        # ok
            b = cfg.rollout.page_watermrk         # typo -> finding
            c = getattr(cfg, "bogus_field")       # finding
            d = getattr(cfg, "maybe", None)       # 3-arg: exempt
            return a, b, c, d
        """,
    }
    findings = [f for f in run_on_files(files)
                if f.rule_id == "config-drift"]
    msgs = " | ".join(f.message for f in findings)
    assert "page_watermrk" in msgs
    assert "bogus_field" in msgs
    assert "maybe" not in msgs
    assert "page_watermark is never read" not in msgs


def test_frame_exhaustive_accepts_loud_else_subset():
    """A dispatch chain that handles a direction SUBSET is fine as
    long as the else rejects loudly — the shipped learner/worker recv
    loops are exactly this shape."""
    src = """
    FRAME_A = 0
    FRAME_B = 1
    FRAME_C = 2

    def dispatch(kind):
        if kind == FRAME_A:
            return 1
        elif kind == FRAME_B:
            return 2
        else:
            raise ValueError(f"unexpected frame {kind}")
    """
    assert "frame-exhaustive" not in ids_of(run_on(src, "wire.py"))


def test_gateway_frame_family_finding_scoped_to_gateway():
    """The ISSUE 12 fixture's finding must land on gateway.py ONLY:
    the pool module's fully-handled chain is judged against the
    frames IT knows, not the gateway's family (the PR 11 scoping
    logic, exercised by its first real in-tree consumer)."""
    pos = next(p for (rid, p, _n, _path) in FIXTURES
               if rid == "frame-exhaustive" and isinstance(p, dict))
    hits = [f for f in run_on_files(pos)
            if f.rule_id == "frame-exhaustive"]
    assert hits
    assert all(f.path.endswith("gateway.py") for f in hits), hits
    assert any("FRAME_CANCEL" in f.message for f in hits)


def test_frame_exhaustive_missing_history_table():
    src = """
    import struct

    PROTOCOL_VERSION = 1
    _HEADER = struct.Struct(">4sH")
    """
    hits = [f for f in run_on(src, "wire.py")
            if f.rule_id == "frame-exhaustive"]
    assert hits and "no version-history table" in hits[0].message


def test_wire_history_mirrors_protocol_version():
    """Runtime twin of the structural check: the shipped remote.py
    header format IS the registered entry for the shipped version."""
    from orion_tpu.orchestration.remote import (_HEADER, _HEADER_HISTORY,
                                                PROTOCOL_VERSION)

    assert _HEADER_HISTORY[PROTOCOL_VERSION] == _HEADER.format
    assert max(_HEADER_HISTORY) == PROTOCOL_VERSION


def test_replica_frame_family_needs_loud_else():
    """The v8 replica membership family (PR 20): a link recv loop
    dispatching FRAME_REPLICA_HB/FRAME_GOODBYE with a loud else is
    the shipped shape and passes; dropping the else silently swallows
    the family's OTHER frame (FRAME_EDGE misrouted onto a membership
    link) and must be a finding."""
    head = """
    import struct

    PROTOCOL_VERSION = 8
    _HEADER = struct.Struct(">4sHBQQQ")
    _HEADER_HISTORY = {8: ">4sHBQQQ"}
    FRAME_GOODBYE = 5
    FRAME_REPLICA_HB = 48
    FRAME_EDGE = 49
    """
    good = head + """
    def link_recv(kind):
        if kind == FRAME_REPLICA_HB:
            return "beat"
        elif kind == FRAME_GOODBYE:
            return "down"
        else:
            raise ValueError(f"unexpected frame {kind}")
    """
    assert "frame-exhaustive" not in ids_of(run_on(good, "wire.py"))

    bad = head + """
    def link_recv(kind):
        if kind == FRAME_REPLICA_HB:
            return "beat"
        elif kind == FRAME_GOODBYE:
            return "down"
    """
    hits = [f for f in run_on(bad, "wire.py")
            if f.rule_id == "frame-exhaustive"]
    assert hits and any("FRAME_EDGE" in f.message for f in hits)


def test_lock_discipline_ignores_foreign_and_constructor_access():
    """__init__ runs before any thread exists and jax/HF config
    objects are not ours — neither may fire."""
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self.state = 0          # pre-thread: never a finding
            self.state += 1

        def bump(self):
            with self._lock:
                self.state += 1

        def read(self):
            with self._lock:
                return self.state
    """
    assert "lock-discipline" not in ids_of(run_on(src, "box.py"))
    jx = """
    import jax

    def tune(cfg):
        jax.config.update("jax_default_matmul_precision", "float32")
        return jax.config.jax_default_matmul_precision
    """
    assert "config-drift" not in ids_of(run_on(jx, "tune.py"))


def test_unused_suppression_ignores_string_literals():
    """The marker inside a STRING (a docstring example, a hint
    template) is prose, not a suppression — tokenize-level comment
    detection, not a line regex."""
    src = '''
    HINT = "justify with # orion: ignore[raw-socket] <why>"

    def doc():
        """Example: x.item()  # orion: ignore[host-sync-in-jit]"""
        return HINT
    '''
    assert "unused-suppression" not in ids_of(run_on(src, "x.py"))


def test_dotted_cache_is_identity_checked_and_keeps_nodes_alive():
    """Regression: the dotted-name cache keyed on id(node) alone —
    CPython recycles ids across differently-lived trees (a rule that
    re-parses snippets), so a recycled id must never serve another
    node's cached resolution.  The fix stores the node in the entry
    (strong ref: a cached id cannot be recycled while the entry lives)
    and identity-checks on hit."""
    import ast as ast_mod

    from orion_tpu.analysis.engine import ModuleContext

    src = "import jax\nx = jax.numpy"
    tree = ast_mod.parse(src)
    ctx = ModuleContext("x.py", src, tree)
    node = tree.body[1].value
    assert ctx.dotted(node) == "jax.numpy"
    # simulate the recycled-id collision: a foreign node whose id slot
    # holds another node's cached entry must MISS, not hit
    foreign = ast_mod.parse("y = torch.numpy").body[0].value
    ctx._dotted_cache[id(foreign)] = (node, "jax.numpy")
    assert ctx.dotted(foreign) == "torch.numpy"
    # and after resolution the entry pins the node it describes
    entry = ctx._dotted_cache[id(foreign)]
    assert entry[0] is foreign and entry[1] == "torch.numpy"


# ---------------------------------------------------------------------------
# result cache: correctness before speed
# ---------------------------------------------------------------------------


def test_cache_edit_invalidates_stale_result(tmp_path, capsys):
    """Edit a file -> its cached per-file result is stale and must not
    be served; validity is the CONTENT hash, so even an edit that
    preserves mtime+size semantics (os.utime rollback) invalidates."""
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("from jax import shard_map\n")
    cache = tmp_path / "cache.json"
    assert main(["--cache", str(cache), str(target)]) == 1
    assert cache.exists()
    st = os.stat(target)
    target.write_text(
        "from orion_tpu.utils.platform import shard_map\n")
    os.utime(target, (st.st_atime, st.st_mtime))  # mtime rolled back
    assert main(["--cache", str(cache), str(target)]) == 0
    capsys.readouterr()


def test_cache_reuses_unchanged_results_and_fingerprint_gates(tmp_path):
    import hashlib

    from orion_tpu.analysis.engine import (ResultCache, analyze_paths,
                                           ruleset_fingerprint)

    target = tmp_path / "mod.py"
    target.write_text("from jax import shard_map\n")
    cache = tmp_path / "cache.json"
    first = analyze_paths([str(target)], cache_path=str(cache))
    assert {f.rule_id for f in first} == {"compat-import"}
    # the entry round-trips bit-identically for unchanged content...
    rc = ResultCache(str(cache), ruleset_fingerprint(None))
    sha = hashlib.sha1(target.read_bytes()).hexdigest()
    hit = rc.get(str(target), sha)
    assert hit is not None and rc.hits == 1
    assert [f.key() for f in hit] == [f.key() for f in first]
    # ...a second full run reports the same findings through the cache
    again = analyze_paths([str(target)], cache_path=str(cache))
    assert [f.key() for f in again] == [f.key() for f in first]
    # ...and a rule-set/package change drops the whole cache
    stale = ResultCache(str(cache), "different-fingerprint")
    assert stale.get(str(target), sha) is None


# ---------------------------------------------------------------------------
# CI formats + baseline workflow
# ---------------------------------------------------------------------------


def test_sarif_output_matches_2_1_0_shape(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    assert main(["--no-cache", "--format", "sarif", str(dirty)]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == "2.1.0"
    assert "sarif-2.1.0" in doc["$schema"]
    run = doc["runs"][0]
    driver = run["tool"]["driver"]
    assert driver["name"] == "orion-tpu-analysis"
    assert {r["id"] for r in driver["rules"]} == \
        {r.id for r in RULES} | {"syntax-error"}
    assert all(r["shortDescription"]["text"] for r in driver["rules"])
    res = run["results"][0]
    assert res["ruleId"] == "compat-import"
    assert res["level"] == "error"
    assert res["message"]["text"]
    loc = res["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"].endswith("dirty.py")
    assert loc["region"]["startLine"] == 1


def test_json_format_and_exit_codes(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    assert main(["--no-cache", "--format", "json", str(dirty)]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["count"] == 1 and doc["baselined"] == 0
    f = doc["findings"][0]
    assert f["rule"] == "compat-import" and f["line"] == 1
    assert f["path"].endswith("dirty.py") and f["hint"]
    clean = tmp_path / "clean.py"
    clean.write_text("X = 1\n")
    assert main(["--no-cache", "--format", "json", str(clean)]) == 0
    assert json.loads(capsys.readouterr().out)["count"] == 0


def test_baseline_warn_first_then_tighten(tmp_path, capsys):
    """The landing workflow for a new rule: --update-baseline records
    today's findings, the gate passes on them (exit 0), a NEW finding
    still gates, and deleting the baseline tightens to the self-gate."""
    from orion_tpu.analysis.__main__ import main

    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    bl = tmp_path / "baseline.json"
    assert main(["--no-cache", "--baseline", str(bl),
                 "--update-baseline", str(dirty)]) == 0
    assert "1 finding" in capsys.readouterr().out
    # baselined: hidden from the gate, surfaced in the summary
    assert main(["--no-cache", "--baseline", str(bl),
                 str(dirty)]) == 0
    assert "baselined" in capsys.readouterr().out
    # a NEW finding (different rule/message) still gates
    dirty.write_text("from jax import shard_map\n"
                     "from jax.lax import axis_size\n")
    assert main(["--no-cache", "--baseline", str(bl),
                 str(dirty)]) == 1
    out = capsys.readouterr().out
    assert "axis_size" in out and "1 baselined" in out
    # tighten: no baseline -> both findings gate again
    assert main(["--no-cache", str(dirty)]) == 1
    assert "2 findings" in capsys.readouterr().out
    # a missing baseline file is a usage error, not a silent pass
    assert main(["--no-cache", "--baseline",
                 str(tmp_path / "nope.json"), str(dirty)]) == 2
    capsys.readouterr()


def test_list_rules_marks_project_vs_file(capsys):
    from orion_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    lines = capsys.readouterr().out.splitlines()
    by_id = {ln.split()[0]: ln for ln in lines if ln.strip()}
    for rid in ("lock-discipline", "frame-exhaustive", "config-drift",
                "lock-order", "blocking-in-pump", "telemetry-drift",
                "fault-coverage"):
        assert "[project]" in by_id[rid]
    assert "[file" in by_id["compat-import"]
    assert "[file" in by_id["unused-suppression"]


def test_cache_hit_reanchors_findings_to_invocation_path(
        tmp_path, monkeypatch):
    """Regression: cache entries are keyed by abspath but findings
    stored the invocation-time path SPELLING — a warm hit via a
    different spelling (relative vs absolute) must re-anchor, or the
    suppression filter misses its context and a justified suppression
    both resurfaces its finding AND reads as stale."""
    from orion_tpu.analysis.engine import analyze_paths

    mod = tmp_path / "mod.py"
    mod.write_text(
        "import socket\n\n"
        "def dial(p):\n"
        "    return socket.create_connection(('h', p))"
        "  # orion: ignore[raw-socket] test probe\n")
    cache = tmp_path / "c.json"
    monkeypatch.chdir(tmp_path)
    assert analyze_paths(["mod.py"], cache_path=str(cache)) == []
    assert analyze_paths([str(mod)], cache_path=str(cache)) == []


def test_bare_stale_suppression_is_itself_reported():
    """Regression: a bracketless ignore must not silence its OWN
    staleness verdict — it silences every rule except
    unused-suppression (which only fires when nothing else does)."""
    hits = run_on("X = 1  # orion: ignore\n")
    assert ids_of(hits) == {"unused-suppression"}


def test_malformed_baseline_is_usage_error_not_crash(tmp_path, capsys):
    """A hand-edited baseline entry missing its keys must exit 2 with
    a message, never escape as a KeyError traceback CI reads as
    'findings found'."""
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("X = 1\n")
    bad = tmp_path / "bad.json"
    bad.write_text('{"findings": [{"rule": "x"}]}')
    assert main(["--no-cache", "--baseline", str(bad),
                 str(target)]) == 2
    assert "unreadable baseline" in capsys.readouterr().err


def test_baseline_counts_occurrences_and_normalizes_paths(
        tmp_path, capsys, monkeypatch):
    """Regressions: (1) one baselined entry must not silently absorb a
    SECOND identical violation — matching is count-based; (2) baseline
    keys are cwd-relative, so a baseline written via a relative path
    matches an absolute invocation of the same file."""
    from orion_tpu.analysis.__main__ import main

    monkeypatch.chdir(tmp_path)
    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    assert main(["--no-cache", "--baseline", "b.json",
                 "--update-baseline", "dirty.py"]) == 0
    # absolute spelling of the same file: still baselined
    assert main(["--no-cache", "--baseline", "b.json",
                 str(dirty)]) == 0
    # a second IDENTICAL violation (same rule+path+message, new line)
    # exceeds the recorded count and gates
    dirty.write_text("from jax import shard_map\n"
                     "from jax import shard_map\n")
    assert main(["--no-cache", "--baseline", "b.json",
                 "dirty.py"]) == 1
    out = capsys.readouterr().out
    assert "1 finding" in out and "1 baselined" in out


def test_config_drift_method_wiring_is_order_independent():
    """Regression: a knob read only by a helper DEFINED BEFORE the
    externally-called method that delegates to it must still count as
    wired (fixpoint, not single definition-order pass)."""
    files = {
        "myconfig.py": """
        import dataclasses

        @dataclasses.dataclass
        class RetryConfig:
            max_tries: int = 3

            def _policy_impl(self):
                return self.max_tries * 2

            def retry_policy(self):
                return self._policy_impl()
        """,
        "caller.py": """
        def go(cfg):
            return cfg.retry_policy()
        """,
    }
    assert "config-drift" not in ids_of(run_on_files(files))


def test_frame_exhaustive_universe_is_module_scoped():
    """Regression: a module fully dispatching its OWN frame family
    must not fail against another module's frames — the missing-set is
    judged per module (frames it defines/imports/mentions), so a
    second family (the streaming-gateway direction) can land without
    poisoning every existing chain."""
    files = {
        "remote.py": """
        FRAME_DATA = 0
        FRAME_HELLO = 1
        FRAME_TRAJ = 2
        """,
        "gateway.py": """
        STREAM_OPEN = 0

        FRAME_X = 10
        FRAME_Y = 11

        def dispatch(kind):
            if kind == FRAME_X:
                return 1
            elif kind == FRAME_Y:
                return 2
        """,
    }
    assert "frame-exhaustive" not in ids_of(run_on_files(files))
    # ...but dropping one of the module's OWN frames still fires
    files["gateway.py"] = files["gateway.py"].replace(
        "FRAME_Y = 11", "FRAME_Y = 11\n        FRAME_Z = 12")
    hits = [f for f in run_on_files(files)
            if f.rule_id == "frame-exhaustive"]
    assert hits and "FRAME_Z" in hits[0].message


def test_syntax_error_survives_rule_filter(tmp_path, capsys):
    """Regression: a --rule-filtered gate must never report clean on a
    file it could not even parse."""
    from orion_tpu.analysis.__main__ import main

    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    assert main(["--no-cache", "--rule", "raw-socket",
                 str(bad)]) == 1
    assert "syntax-error" in capsys.readouterr().out


def test_string_literal_marker_neither_suppresses_nor_audits():
    """Regression: is_suppressed and the unused-suppression sweep now
    share the tokenized comment map — a marker inside a string
    literal is prose on BOTH sides: it cannot swallow a real finding,
    and it is never judged stale."""
    src = """
    import socket

    def dial(p):
        return socket.create_connection(("h", p)), "# orion: ignore"
    """
    got = ids_of(run_on(src, "orion_tpu/fake_io.py"))
    assert "raw-socket" in got          # the string did not suppress
    assert "unused-suppression" not in got


def test_cache_sections_let_rule_selections_coexist(tmp_path):
    """Regression: alternating full-registry and --rule invocations
    share one cache file via per-fingerprint sections instead of
    wholesale-evicting each other."""
    import hashlib

    from orion_tpu.analysis.engine import (ResultCache, analyze_paths,
                                           ruleset_fingerprint)

    target = tmp_path / "mod.py"
    target.write_text("from jax import shard_map\n")
    cache = tmp_path / "c.json"
    only = [r for r in RULES if r.id == "raw-socket"]
    analyze_paths([str(target)], cache_path=str(cache))          # full
    analyze_paths([str(target)], rules=only, cache_path=str(cache))
    sha = hashlib.sha1(target.read_bytes()).hexdigest()
    rc_full = ResultCache(str(cache), ruleset_fingerprint(None))
    rc_rule = ResultCache(str(cache), ruleset_fingerprint(only))
    assert rc_full.get(str(target), sha) is not None
    assert rc_rule.get(str(target), sha) is not None


def test_non_dict_baseline_is_usage_error(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("X = 1\n")
    bad = tmp_path / "bl.json"
    bad.write_text("[]")
    assert main(["--no-cache", "--baseline", str(bad),
                 str(target)]) == 2
    assert "unreadable baseline" in capsys.readouterr().err


def test_overlapping_paths_do_not_duplicate_project_modules(tmp_path):
    """Regression: a dir plus a file inside it must analyze the file
    ONCE — a duplicated module makes every lock-owning class's methods
    ambiguously owned, silently disabling thread-entry resolution."""
    from orion_tpu.analysis.engine import iter_python_files

    mod = tmp_path / "pool.py"
    mod.write_text("X = 1\n")
    files = list(iter_python_files([str(tmp_path), str(mod)]))
    assert len(files) == 1


def test_lock_discipline_sees_annotated_lock_assignment():
    """Regression: `self._lock: threading.Lock = threading.Lock()`
    must register lock ownership exactly like the bare assignment."""
    src = """
    import queue
    import threading

    class Pool:
        def __init__(self):
            self._lock: threading.Lock = threading.Lock()
            self.alive = True
            self.inbox = queue.Queue()
            self._t = threading.Thread(target=self._recv_loop)

        def consume(self):
            with self._lock:
                if self.alive:
                    return self.inbox.get_nowait()
                return None

        def shutdown(self):
            with self._lock:
                self.alive = False

        def _recv_loop(self):
            while self.alive:
                self.inbox.put(1)
    """
    assert "lock-discipline" in ids_of(run_on(src, "pool.py"))


def test_frame_exhaustive_credits_else_with_nested_if():
    """Regression: an `else:` whose body is one nested `if` that
    raises/logs is a loud catch-all, not a silent elif — col_offset
    distinguishes it from a real elif."""
    src = """
    import logging

    FRAME_A = 0
    FRAME_B = 1
    FRAME_C = 2

    def dispatch(kind):
        if kind == FRAME_A:
            return 1
        elif kind == FRAME_B:
            return 2
        else:
            if kind != 99:
                logging.getLogger(__name__).warning(
                    "unexpected frame %s", kind)
    """
    assert "frame-exhaustive" not in ids_of(run_on(src, "wire.py"))


def test_frame_exhaustive_counts_renamed_imports():
    """Regression: `from remote import FRAME_C as GOODBYE` still owes
    FRAME_C a branch — the local universe resolves alias TARGETS."""
    files = {
        "remote.py": """
        FRAME_A = 0
        FRAME_B = 1
        FRAME_C = 2
        """,
        "client.py": """
        from remote import FRAME_A, FRAME_B
        from remote import FRAME_C as GOODBYE

        def dispatch(kind):
            if kind == FRAME_A:
                return 1
            elif kind == FRAME_B:
                return 2
        """,
    }
    hits = [f for f in run_on_files(files)
            if f.rule_id == "frame-exhaustive"]
    assert hits and "FRAME_C" in hits[0].message


def test_lock_discipline_trusts_caller_held_helpers():
    """Regression: a helper only ever called with the lock held (the
    _mark_dead style) must not be flagged — nor may the exemption
    leak to a helper that ALSO has a bare call site."""
    base = """
    import threading

    class Pool:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self._loop)

        def read(self):
            with self._lock:
                return self.count

        def snap(self):
            with self._lock:
                return self.count + 1

        def _bump(self):
            self.count += 1

        def _loop(self):
            with self._lock:
                self._bump()
    """
    assert "lock-discipline" not in ids_of(run_on(base, "pool.py"))
    leaky = base.replace(
        "            with self._lock:\n                self._bump()",
        "            with self._lock:\n                self._bump()\n"
        "            self._bump()")
    assert "lock-discipline" in ids_of(run_on(leaky, "pool.py"))


def test_config_drift_store_only_knob_is_unwired():
    """Regression: `cfg.knob = 5` is a STORE — it must not count as
    the read that wires a knob."""
    files = {
        "myconfig.py": """
        import dataclasses

        @dataclasses.dataclass
        class ServeConfig:
            write_only: int = 0
        """,
        "launch.py": """
        def wire(cfg):
            cfg.write_only = 5
        """,
    }
    hits = [f for f in run_on_files(files)
            if f.rule_id == "config-drift"]
    assert hits and "write_only" in hits[0].message


def test_sarif_declares_syntax_error_rule(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    assert main(["--no-cache", "--format", "sarif", str(bad)]) == 1
    doc = json.loads(capsys.readouterr().out)
    run = doc["runs"][0]
    declared = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {r["ruleId"] for r in run["results"]} <= declared


def test_baseline_matches_across_invoking_cwds(tmp_path, capsys,
                                               monkeypatch):
    """Regression: baseline keys anchor to the BASELINE FILE's
    directory, so a baseline written from one cwd keeps matching when
    the gate later runs from a subdirectory."""
    from orion_tpu.analysis.__main__ import main

    dirty = tmp_path / "dirty.py"
    dirty.write_text("from jax import shard_map\n")
    sub = tmp_path / "sub"
    sub.mkdir()
    monkeypatch.chdir(tmp_path)
    assert main(["--no-cache", "--baseline", "b.json",
                 "--update-baseline", "dirty.py"]) == 0
    monkeypatch.chdir(sub)
    assert main(["--no-cache", "--baseline", "../b.json",
                 "../dirty.py"]) == 0
    capsys.readouterr()


def test_lock_alias_keyword_condition_form():
    """Regression: `threading.Condition(lock=self._lock)` aliases the
    lock exactly like the positional form — the per-lock evidence must
    not split across two names."""
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._cv = threading.Condition(lock=self._lock)
            self.n = 0
            self._t = threading.Thread(target=self._loop)

        def read(self):
            with self._cv:
                return self.n

        def bump(self):
            with self._lock:
                self.n += 1

        def _loop(self):
            while self.n < 3:
                pass
    """
    assert "lock-discipline" in ids_of(run_on(src, "box.py"))


def test_fingerprint_is_rule_order_independent():
    from orion_tpu.analysis.engine import ruleset_fingerprint

    a = [r for r in RULES if r.id in ("raw-socket", "naked-timer")]
    assert ruleset_fingerprint(a) == \
        ruleset_fingerprint(list(reversed(a)))


def test_baseline_never_absorbs_syntax_errors(tmp_path, capsys,
                                              monkeypatch):
    """Regression: an unparsable file must gate even when its finding
    was present at --update-baseline time — a baselined gate must
    never stay green on a file that does not parse."""
    from orion_tpu.analysis.__main__ import main

    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    assert main(["--no-cache", "--baseline", "b.json",
                 "--update-baseline", "broken.py"]) == 0
    assert main(["--no-cache", "--baseline", "b.json",
                 "broken.py"]) == 1
    assert "syntax-error" in capsys.readouterr().out


def test_header_history_lookup_is_name_tied():
    """Regression: an unrelated *_HISTORY dict in the same module must
    not clobber the header's own table."""
    src = """
    import struct

    PROTOCOL_VERSION = 2
    _HEADER = struct.Struct(">4sHB")
    _HEADER_HISTORY = {1: ">4sH", 2: ">4sHB"}
    _RETRY_HISTORY = {1: "connect"}
    """
    assert "frame-exhaustive" not in ids_of(run_on(src, "wire.py"))


def test_malformed_cache_entry_degrades_to_miss(tmp_path):
    from orion_tpu.analysis.engine import (ResultCache, analyze_paths,
                                           ruleset_fingerprint)

    target = tmp_path / "mod.py"
    target.write_text("from jax import shard_map\n")
    cache = tmp_path / "c.json"
    analyze_paths([str(target)], cache_path=str(cache))
    # corrupt the per-file entry but keep valid JSON + sections shape
    fp = ruleset_fingerprint(None)
    cache.write_text(json.dumps(
        {"sections": {fp: {str(target).replace(os.sep, "/"):
                           "not-a-dict"}}}))
    findings = analyze_paths([str(target)], cache_path=str(cache))
    assert {f.rule_id for f in findings} == {"compat-import"}


def test_cache_is_path_spelling_scoped(tmp_path, monkeypatch):
    """Regression: rule output depends on the path SPELLING (test/obs
    exemptions), so a cache entry for one spelling must never serve
    another — here the same bytes are exempt as `tests/x.py` but must
    still fire as `pkg/x.py`."""
    from orion_tpu.analysis.engine import analyze_paths

    (tmp_path / "tests").mkdir()
    (tmp_path / "pkg").mkdir()
    snippet = ("import time\n\n"
               "def measure(f):\n"
               "    t0 = time.monotonic()\n"
               "    f()\n"
               "    return time.monotonic() - t0\n")
    (tmp_path / "tests" / "x.py").write_text(snippet)
    (tmp_path / "pkg" / "x.py").write_text(snippet)
    cache = tmp_path / "c.json"
    monkeypatch.chdir(tmp_path)
    assert analyze_paths(["tests/x.py"],
                         cache_path=str(cache)) == []
    hits = analyze_paths(["pkg/x.py"], cache_path=str(cache))
    assert "naked-timer" in {f.rule_id for f in hits}


def test_lock_discipline_flags_wrong_lock_access():
    """Regression: an access under a DIFFERENT lock than the guarding
    one is no mutual exclusion — 'some lock held' must not pass."""
    src = """
    import threading

    class Box:
        def __init__(self):
            self._lock = threading.Lock()
            self._other = threading.Lock()
            self.count = 0
            self._t = threading.Thread(target=self.run)

        def read(self):
            with self._lock:
                return self.count

        def snap(self):
            with self._lock:
                return self.count + 1

        def run(self):
            with self._other:
                self.count += 1
    """
    hits = [f for f in run_on(src, "box.py")
            if f.rule_id == "lock-discipline"]
    assert hits and "DIFFERENT" in hits[0].message


def test_config_drift_annotated_module_constant_is_legal():
    """Regression: `NAME: dict = {...}` at config-module top level is
    a legal `config.NAME` read target (AnnAssign, not just Assign)."""
    files = {
        "myconfig.py": """
        import dataclasses

        DEFAULT_PROFILES: dict = {"a": 1}

        @dataclasses.dataclass
        class ServeConfig:
            port: int = 0
        """,
        "server.py": """
        from myproj import myconfig as config

        def serve(cfg):
            return cfg.port, config.DEFAULT_PROFILES
        """,
    }
    assert "config-drift" not in ids_of(run_on_files(files))


def test_malformed_history_key_reports_not_crashes():
    """Regression: a string-key typo in the history table must yield a
    finding, never a TypeError out of the analyzer."""
    src = """
    import struct

    PROTOCOL_VERSION = 4
    _HEADER = struct.Struct(">4sHBQQQ")
    _HEADER_HISTORY = {"3": ">4sHBQ", 4: ">4sHBQQQ"}
    """
    run_on(src, "wire.py")  # must not raise
    src2 = src.replace('4: ">4sHBQQQ"', '"4": ">4sHBQQQ"')
    hits = [f for f in run_on(src2, "wire.py")
            if f.rule_id == "frame-exhaustive"]
    assert hits  # all entries malformed -> format unregistered


def test_corrupt_cache_section_degrades_to_miss(tmp_path):
    """Regression: a non-dict SECTION value (hand edit / disk
    corruption) must degrade to a cold run, never a traceback."""
    from orion_tpu.analysis.engine import (analyze_paths,
                                           ruleset_fingerprint)

    target = tmp_path / "mod.py"
    target.write_text("from jax import shard_map\n")
    cache = tmp_path / "c.json"
    fp = ruleset_fingerprint(None)
    cache.write_text(json.dumps({"sections": {fp: [1, 2, 3]}}))
    findings = analyze_paths([str(target)], cache_path=str(cache))
    assert {f.rule_id for f in findings} == {"compat-import"}
    # and the corrupt section did not round-trip
    data = json.loads(cache.read_text())
    assert isinstance(data["sections"][fp], dict)


def test_unwritable_baseline_path_is_usage_error(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("X = 1\n")
    missing = tmp_path / "nodir" / "b.json"
    assert main(["--no-cache", "--baseline", str(missing),
                 "--update-baseline", str(target)]) == 2
    assert "cannot write baseline" in capsys.readouterr().err


def test_cyclic_config_inheritance_degrades_not_crashes():
    """Regression: statically-cyclic *Config bases (a typo'd base on
    WIP code parses fine) must not RecursionError the gate."""
    files = {
        "myconfig.py": """
        import dataclasses

        @dataclasses.dataclass
        class AConfig(BConfig):
            x: int = 0

        @dataclasses.dataclass
        class BConfig(AConfig):
            y: int = 0

        @dataclasses.dataclass
        class TopConfig:
            sub: AConfig = dataclasses.field(default_factory=AConfig)
        """,
        "server.py": """
        def go(cfg):
            return cfg.sub.x + cfg.sub.y + cfg.sub.x
        """,
    }
    run_on_files(files)  # must not raise


def test_every_header_is_validated_not_just_the_last():
    """Regression: a second wire header later in the module must not
    mask the first header's unbumped format edit."""
    src = """
    import struct

    PROTOCOL_VERSION = 4
    _HEADER = struct.Struct(">4sHBQQQ")
    _HEADER_HISTORY = {4: ">4sHBQ"}

    _DIAG_HEADER = struct.Struct(">4sH")
    _DIAG_HEADER_HISTORY = {4: ">4sH"}
    """
    hits = [f for f in run_on(src, "wire.py")
            if f.rule_id == "frame-exhaustive"]
    assert hits and "_HEADER pack format" in hits[0].message


def test_cache_prune_bounds_growth_without_subset_wipe(tmp_path):
    """Regression pair: stale one-off entries are shed past the bound,
    but an ad-hoc single-file run must not wipe a full-tree section."""
    from orion_tpu.analysis.engine import ResultCache

    rc = ResultCache(str(tmp_path / "c.json"), "fp")
    for i in range(1030):
        rc.put(f"gone/{i}.py", "sha", [])
    rc.put("keep.py", "sha", [])
    rc.prune(["keep.py"])                    # over the bound: shed
    assert len(rc._files) == 1024 and "keep.py" in rc._files
    small = ResultCache(str(tmp_path / "d.json"), "fp")
    for i in range(50):
        small.put(f"tree/{i}.py", "sha", [])
    small.prune(["tree/0.py"])               # under the bound: keep
    assert len(small._files) == 50


def test_no_project_flag_enables_partial_path_runs(capsys):
    """A single-file run of config.py would flag every knob whose
    reader lives elsewhere; --no-project withholds project findings
    (while still judging project-id suppressions correctly)."""
    from orion_tpu.analysis.__main__ import main

    cfg = os.path.join(REPO, "orion_tpu", "config.py")
    assert main(["--no-cache", cfg]) == 1        # scoped noise
    assert "config-drift" in capsys.readouterr().out
    assert main(["--no-cache", "--no-project", cfg]) == 0
    capsys.readouterr()


def test_no_project_with_project_only_rule_is_usage_error(tmp_path):
    """`--no-project --rule lock-discipline` would check nothing — a
    run that checks nothing must not report clean."""
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("X = 1\n")
    with pytest.raises(SystemExit) as exc:
        main(["--no-cache", "--no-project",
              "--rule", "lock-discipline", str(target)])
    assert exc.value.code == 2


def test_bytes_struct_format_headers_pass():
    """Regression: struct.Struct accepts bytes formats — a matching
    bytes header/history pair must pass, mixed str/bytes too."""
    src = """
    import struct

    PROTOCOL_VERSION = 2
    _HEADER = struct.Struct(b">4sHB")
    _HEADER_HISTORY = {1: ">4sH", 2: b">4sHB"}
    """
    assert "frame-exhaustive" not in ids_of(run_on(src, "wire.py"))


def test_is_test_path_matches_segments_not_substrings():
    from orion_tpu.analysis.engine import is_test_path

    assert is_test_path("tests/test_x.py")
    assert is_test_path("pkg/tests/helper.py")
    assert is_test_path("conftest.py")
    assert not is_test_path("orion_tpu/backtests/driver.py")
    assert not is_test_path("orion_tpu/contests.py")


# ---------------------------------------------------------------------------
# phase 3: the interprocedural call-graph rules (ISSUE 19)
# ---------------------------------------------------------------------------


def _fixture_pos(rid):
    """The positive multi-file fixture registered above for ``rid``."""
    return next(p for (r, p, _n, _pth) in FIXTURES
                if r == rid and isinstance(p, dict))


def test_lock_order_witness_names_the_full_path():
    """Acceptance criterion: the deadlock finding carries the WHOLE
    witness — the lock cycle AND the concrete hold-then-acquire chain
    with methods and call sites, so the reader can walk the inversion
    without re-running the analyzer."""
    hits = [f for f in run_on_files(_fixture_pos("lock-order"))
            if f.rule_id == "lock-order"]
    assert len(hits) == 1, hits
    msg = hits[0].message
    assert "lock acquisition cycle" in msg
    assert "Gateway._lock -> Pool._lock -> Gateway._lock" in msg
    assert "Gateway.route holds Gateway._lock" in msg
    assert "Pool.mark_dead" in msg
    assert "lo_gw.py" in msg and "lo_pool.py" in msg
    assert "acquires" in msg


def test_blocking_in_pump_witness_names_the_call_chain():
    """Acceptance criterion: the finding names the pump root and every
    hop down to the blocking primitive."""
    hits = [f for f in run_on_files(_fixture_pos("blocking-in-pump"))
            if f.rule_id == "blocking-in-pump"]
    assert len(hits) == 1, hits
    msg = hits[0].message
    assert "time.sleep()" in msg
    assert "pump root Gateway.step" in msg
    assert "Gateway.step -> Gateway._drain -> Gateway._wait_ready" \
        in msg
    # ...and the finding anchors at the blocking CALL SITE
    assert hits[0].path.endswith("bp_gw.py")


def test_lock_order_released_then_reacquired_is_no_cycle():
    """Edge case: sequential ``with self._lock:`` blocks RELEASE
    between acquisitions — a cross-class call AFTER the with exits
    holds nothing, so neither direction contributes an ordering edge
    even when both classes call into each other."""
    files = {
        "orion_tpu/orchestration/rr_a.py": """
        import threading

        class Alpha:
            def __init__(self, beta):
                self._lock = threading.Lock()
                self.beta = beta
                self.n = 0

            def poke(self):
                with self._lock:
                    self.n += 1
                self.beta.nudge()
        """,
        "orion_tpu/orchestration/rr_b.py": """
        import threading

        class Beta:
            def __init__(self, alpha):
                self._lock = threading.Lock()
                self.alpha = alpha
                self.m = 0

            def nudge(self):
                with self._lock:
                    self.m += 1
                self.alpha.poke()
        """,
    }
    assert "lock-order" not in ids_of(run_on_files(files))


def test_blocking_in_pump_flags_dead_branch_conservatively():
    """Edge case, documented conservatism: the call graph is
    control-flow-INSENSITIVE by contract (callgraph.py), so a blocking
    call in a statically-dead branch of a pump method still fires —
    over-approximation is the design, per-line suppression the escape
    hatch for a justified one."""
    files = {
        "orion_tpu/orchestration/db_gw.py": """
        import time

        class Gateway:
            def step(self):
                if False:
                    time.sleep(1.0)
        """,
    }
    hits = [f for f in run_on_files(files)
            if f.rule_id == "blocking-in-pump"]
    assert hits and "time.sleep" in hits[0].message


def test_telemetry_fstring_key_matches_by_prefix():
    """Edge case: a producer emitting f-string keys
    (``tenant_{t}_shed``) is matched as a (prefix, suffix) pattern —
    both a literal consumed key inside the pattern and a
    startswith-style pattern consumer count as wired."""
    files = {
        "orion_tpu/obs/fs_prod.py": """
        class Telemetry:
            def server_stats(self):
                out = {}
                for t in ("a", "b"):
                    out[f"tenant_{t}_shed"] = 1.0
                return out
        """,
        "orion_tpu/rollout/fs_cons.py": """
        def watch(t):
            stats = t.server_stats()
            shed = [v for k, v in stats.items()
                    if k.startswith("tenant_")]
            return shed, stats["tenant_a_shed"]
        """,
    }
    assert "telemetry-drift" not in ids_of(run_on_files(files))


PHASE3_RULE_IDS = ("lock-order", "blocking-in-pump", "telemetry-drift",
                   "fault-coverage")


def test_each_phase3_rule_is_suppressible():
    """Every phase-3 finding obeys the same per-line suppression
    contract as the rest of the registry — and a USED suppression is
    never judged stale by the unused-suppression sweep."""
    from orion_tpu.analysis import analyze_sources as run_raw

    for rid in PHASE3_RULE_IDS:
        files = {p: textwrap.dedent(s)
                 for p, s in _fixture_pos(rid).items()}
        hits = [f for f in run_raw(list(files.items()))
                if f.rule_id == rid]
        assert hits, f"{rid}: positive fixture went quiet"
        for path, line in {(f.path, f.line) for f in hits}:
            rows = files[path].split("\n")
            rows[line - 1] += f"  # orion: ignore[{rid}] justified"
            files[path] = "\n".join(rows)
        again = ids_of(run_raw(list(files.items())))
        assert rid not in again, f"{rid}: suppression did not silence"
        assert "unused-suppression" not in again, \
            f"{rid}: live suppression judged stale"


def test_changed_mode_keeps_project_rule_parity(tmp_path, monkeypatch,
                                                capsys):
    """--changed scopes the PER-FILE phase to files changed vs
    `git merge-base HEAD main`, but the project phase always sees the
    full tree — so project-rule findings are identical to a full run
    while an unchanged file's per-file findings are skipped."""
    from orion_tpu.analysis.__main__ import main

    env = dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")

    def git(*args):
        subprocess.run(["git", *args], cwd=tmp_path, check=True,
                       capture_output=True, env=env)

    (tmp_path / "myconfig.py").write_text(textwrap.dedent("""
        import dataclasses
        from jax import shard_map

        @dataclasses.dataclass
        class ServeConfig:
            port: int = 0
            orphan_knob: int = 2
    """))
    (tmp_path / "server.py").write_text(
        "def serve(cfg):\n    return cfg.port\n")
    git("init", "-q", "-b", "main")
    git("add", ".")
    git("commit", "-qm", "seed")
    (tmp_path / "helper.py").write_text("from jax import shard_map\n")
    monkeypatch.chdir(tmp_path)
    paths = ["myconfig.py", "server.py", "helper.py"]

    assert main(["--no-cache", "--format", "json", *paths]) == 1
    full = json.loads(capsys.readouterr().out)["findings"]
    assert main(["--no-cache", "--changed", "--format", "json",
                 *paths]) == 1
    part = json.loads(capsys.readouterr().out)["findings"]

    def keyed(findings, rule):
        return {(f["rule"], f["path"], f["line"])
                for f in findings if f["rule"] == rule}

    # project-rule parity: identical finding sets
    assert keyed(full, "config-drift") and \
        keyed(full, "config-drift") == keyed(part, "config-drift")
    # the changed (untracked) file's per-file finding is present...
    assert ("compat-import", "helper.py", 1) in keyed(part,
                                                      "compat-import")
    # ...the unchanged committed file's per-file finding is skipped
    assert any(p == "myconfig.py"
               for _r, p, _l in keyed(full, "compat-import"))
    assert not any(p == "myconfig.py"
                   for _r, p, _l in keyed(part, "compat-import"))


def test_fix_suppressions_roundtrip(tmp_path):
    """--fix-suppressions surgery: a stale bracketed comment is
    deleted, a stale id inside a multi-id bracket is excised keeping
    the live one, a LIVE suppression is untouched byte-for-byte, the
    fixed file lints clean, and a second pass is a no-op."""
    from orion_tpu.analysis.engine import fix_suppressions

    live = ('def dial(p):\n'
            '    return socket.create_connection(("h", p))'
            '  # orion: ignore[raw-socket] probe\n')
    src = ('import socket\n\n'
           + live
           + '\n'
           'def dial2(p):\n'
           '    return socket.create_connection(("h", p))'
           '  # orion: ignore[raw-socket, naked-timer] mixed\n'
           '\n'
           'X = 1  # orion: ignore[prng-reuse] fully stale\n')
    mod = tmp_path / "mod.py"
    mod.write_text(src)
    edits = fix_suppressions([str(mod)])
    assert sorted(line for _p, line in edits) == [7, 9]
    out = mod.read_text()
    assert live in out                                   # untouched
    assert "# orion: ignore[raw-socket] mixed" in out    # id excised
    assert "prng-reuse" not in out                       # comment gone
    assert out.splitlines()[8] == "X = 1"
    assert analyze_paths([str(mod)]) == []               # lints clean
    assert fix_suppressions([str(mod)]) == []            # idempotent
    assert mod.read_text() == out


def test_cache_size_cap_evicts_oldest_section_not_active(tmp_path):
    """The byte-size cap sheds whole sections oldest-first, but the
    ACTIVE section survives even when it alone exceeds the cap — a
    size limit must never wipe the run that is saving."""
    from orion_tpu.analysis.engine import ResultCache

    path = str(tmp_path / "c.json")
    pad = "x" * 2000
    rc1 = ResultCache(path, "fp-old", max_bytes=50_000)
    for i in range(20):
        rc1.put(f"a/{i}.py", pad, [])
    rc1.save()
    rc2 = ResultCache(path, "fp-new", max_bytes=50_000)
    for i in range(20):
        rc2.put(f"b/{i}.py", pad, [])
    rc2.save()
    data = json.loads(open(path).read())
    assert "fp-new" in data["sections"]        # active survives
    assert "fp-old" not in data["sections"]    # oldest shed past cap
    rc3 = ResultCache(path, "fp-solo", max_bytes=1_000)
    for i in range(20):
        rc3.put(f"c/{i}.py", pad, [])
    rc3.save()
    data = json.loads(open(path).read())
    assert "fp-solo" in data["sections"]       # lone over-cap: kept


def test_cli_stats_line(tmp_path, capsys):
    """--stats prints the one-line run summary (files, rules,
    findings, cache hit rate, wall) on stderr, leaving stdout clean
    for the machine formats."""
    from orion_tpu.analysis.__main__ import main

    target = tmp_path / "mod.py"
    target.write_text("X = 1\n")
    assert main(["--no-cache", "--stats", str(target)]) == 0
    captured = capsys.readouterr()
    assert captured.out == ""
    err = captured.err
    assert "stats: files=1" in err and "findings=0" in err
    assert "cache=0/0" in err and "wall=" in err
