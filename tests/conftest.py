"""Test harness: 8 fake CPU devices (SURVEY.md §4), or the real TPU
for the smoke suite.

The CPU pin happens here, while conftest is imported and before any
backend is used: jax initialises backends lazily, so ``JAX_PLATFORMS``,
``XLA_FLAGS`` and the ``jax_platforms`` config value set now are what
the first ``jax.devices()`` sees.  Nothing here describes a TPU
topology, loads libtpu or touches ``jax.devices()`` at import — every
xdist worker must collect the same tests.

TPU-gated regression suite (VERDICT r2 next #3): ``pytest -m tpu`` (or
ORION_TEST_TPU=1) keeps the real TPU backend instead of forcing CPU and
runs only the ``@pytest.mark.tpu`` smoke tests — the pre-bench gate for
kernel/Mosaic regressions the CPU interpret-mode suite cannot see (the
flash odd-cache-length compile failure of commit c0f7905 is the
canonical example).  README documents the command.
"""

import os
import sys


def _tpu_run_requested() -> bool:
    if os.environ.get("ORION_TEST_TPU") == "1":
        return True
    # Exactly `pytest -m tpu` — substring matching would catch
    # `-m "not tpu"` and silently run the whole CPU suite against the
    # real TPU backend.  (Excluding the smoke suite needs no -m at
    # all: tpu-marked tests auto-skip on a non-TPU run.)
    argv = sys.argv
    for i, a in enumerate(argv):
        if a == "-m" and i + 1 < len(argv) and argv[i + 1].strip() == "tpu":
            return True
        if a.startswith("-m") and a[2:].strip() == "tpu":
            return True
    return False


TPU_RUN = _tpu_run_requested()

import jax  # noqa: E402

if not TPU_RUN:
    from orion_tpu.utils.platform import force_cpu_platform

    force_cpu_platform(8)
    jax.config.update("jax_default_matmul_precision", "highest")
    # Persistent XLA compile cache for the CPU suite (ISSUE 12): the
    # tests build dozens of tiny engines whose jitted programs are
    # BYTE-IDENTICAL across instances, but jax.jit's in-memory cache
    # is per-closure so every engine recompiled them from scratch —
    # measured ~45% of test_continuous.py's wall.  The disk cache is
    # content-keyed (backend + jaxlib version + lowered HLO), so
    # cross-run reuse is exactly as sound as jit's own cache;
    # min_compile_time 0 because tiny-model programs all compile in
    # well under the default threshold.  Opt out with
    # ORION_TEST_NO_COMPILE_CACHE=1 (e.g. when timing compiles).
    if os.environ.get("ORION_TEST_NO_COMPILE_CACHE") != "1":
        from orion_tpu.utils.platform import enable_compile_cache

        # JAX_COMPILATION_CACHE_DIR if the environment set it, else the
        # one fixed in-checkout directory every entry point shares.
        cache_dir = enable_compile_cache()
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        # Child processes too (multihost 2-process runs, pool-worker
        # re-execs): they import jax fresh, so the env-var spelling
        # reaches them where this process's jax.config cannot.
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir)
        os.environ.setdefault(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    # Zero-egress box: tell the HF stack so instead of letting every
    # cache-miss dataset/tokenizer lookup spin on connect timeouts —
    # the two offline-error-path tests each burned ~20 s waiting for
    # the network stack to give up on a box that HAS no network.
    # Local-path fixture loads are unaffected (they never consult the
    # hub), and the "not available offline" error contract is
    # identical, just immediate.
    os.environ.setdefault("HF_HUB_OFFLINE", "1")
    os.environ.setdefault("HF_DATASETS_OFFLINE", "1")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: on-chip smoke test (runs only under "
        "`pytest -m tpu` / ORION_TEST_TPU=1 on a TPU box)")
    config.addinivalue_line(
        "markers", "smoke: fast pre-commit gate (`pytest -m smoke`, "
        "<5 min) — the dryrun artifact + one bf16 test per parallelism "
        "strategy + a tiny trainer loop; the full suite is the nightly")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 gate "
        "(`-m 'not slow'`) — wall-clock-heavy scenarios (e.g. watchdog "
        "stall detection) that the nightly full suite still runs")
    # xdist's load scheduling hands a worker that has just run slow
    # tests half its share of everything pending as ONE run of
    # consecutive tests, so where that run fell decided the wall: 35 of
    # ``test_chip_compile.py``'s cases on one worker kept it busy for
    # 1300 s while five idled, and 31 new tests elsewhere were enough
    # to move the boundary there.  A worker is topped up a few tests at
    # a time instead (an explicit --maxschedchunk wins).
    if getattr(config.option, "maxschedchunk", 0) is None:
        config.option.maxschedchunk = SCHED_CHUNK


# This file's compiles for a described chip run on many threads each
# and, where their results are not kept yet (``_compile_once`` there),
# are half of the suite's CPU seconds.
COMPILES_FIRST = "test_chip_compile.py"
SCHED_CHUNK = 4


def pytest_collection_modifyitems(config, items):
    # The long compiles start the run, dealt out SCHED_CHUNK at a time
    # to whichever worker is free, and the short tests fill the end: a
    # stable sort, the same in every worker.
    items.sort(key=lambda item: item.path.name != COMPILES_FIRST)
    skip_tpu = pytest.mark.skip(
        reason="TPU smoke: run with `pytest -m tpu` on a TPU box")
    for item in items:
        if "tpu" in item.keywords and (
                not TPU_RUN or jax.default_backend() != "tpu"):
            item.add_marker(skip_tpu)
