"""AOT compiles for a DESCRIBED v5e chip (no chip attached): the
kernels of the main path at real widths, through the TPU compiler.

This is rehearsal 3 of /opt/skills/guides/on-chip-measurement §2: it
shows what interpret mode cannot (tiling, VMEM, Mosaic lowering rules)
at no chip time.  A compile that passes is not a chip run.

Rules this file keeps (the suite runs under ``-p xdist -n 6 --dist
load``; every worker imports every test file):

- the topology is described INSIDE a module-scoped fixture that skips
  on failure — never at import, in a ``skipif``, in ``parametrize``, in
  conftest, ``autouse`` or a child process: only one process may load
  libtpu, and a module that decides at import which tests exist gives
  the workers different collections (xdist then runs nothing);
- all such tests live in THIS one file (a second file could land on a
  worker that cannot load the library and skip in silence);
- the persistent compile cache is off around these compiles: an entry
  written for a described chip cannot be read back without one.  What
  the tests read off a compiled program, its text and its memory
  analysis, needs no chip: ``_compile_once`` keeps those two under the
  compile cache's directory by the digest of the compiler's whole input
  (these compiles were half of the suite's CPU seconds, 4700 of them,
  the same every run; ``ORION_TEST_NO_COMPILE_CACHE=1`` compiles anew).

``interpret_mode()`` asks the default backend (CPU here); the tests
steer it with monkeypatch — the program gets no option for it.
"""

import contextlib
import hashlib
import json
import os
import types
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

BF16 = jnp.bfloat16
TOPOLOGY = "v5e:2x2"    # the one chip described here; in every digest


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name=TOPOLOGY)
    except Exception as e:
        pytest.skip(f"no {TOPOLOGY} topology can be described here: {e}")
    with _compile_cache_off() as was_on:
        patch = pytest.MonkeyPatch()
        if was_on and jax.config.jax_compilation_cache_dir:
            patch.setattr(jax.stages.Lowered, "compile", _compile_once)
        yield t
        patch.undo()


@contextlib.contextmanager
def _compile_cache_off():
    """jax's persistent compile cache off inside; yields whether it was
    on."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield prev
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


class _Compiled:
    """The two things this file reads off a compiled program."""

    def __init__(self, text: str, memory: dict):
        self._text, self._memory = text, types.SimpleNamespace(**memory)

    def as_text(self) -> str:
        return self._text

    def memory_analysis(self):
        return self._memory


_MEMORY = ("alias_size_in_bytes", "argument_size_in_bytes",
           "generated_code_size_in_bytes", "output_size_in_bytes",
           "peak_memory_in_bytes", "temp_size_in_bytes")
_real_compile = jax.stages.Lowered.compile


def _kept_dir() -> str:
    return os.path.join(jax.config.jax_compilation_cache_dir,
                        "described_chip")


def _compile_once(lowered, *args, **kwargs):
    """``Lowered.compile`` while this module's tests run: the compiled
    text and memory analysis of a program the compiler has seen before
    (the same lowered module with its kernels' bodies, the same chip,
    jax, jaxlib and libtpu, the same flags in the environment) are read
    back from ``<compile cache>/described_chip/<digest>``; anything
    else is compiled, and kept if it compiles.  A refusal is never
    kept."""
    import importlib.metadata as md

    if args or kwargs:
        return _real_compile(lowered, *args, **kwargs)
    versions = [md.version(name) for name in ("jax", "jaxlib", "libtpu")]
    flags = [os.environ.get(name, "") for name in ("XLA_FLAGS",
                                                   "LIBTPU_INIT_ARGS")]
    digest = hashlib.sha256("\0".join(
        [TOPOLOGY] + versions + flags + [lowered.as_text(debug_info=True)]
    ).encode()).hexdigest()
    path = os.path.join(_kept_dir(), digest)
    try:
        with open(path, "rb") as f:
            kept = json.loads(zlib.decompress(f.read()))
        return _Compiled(kept["text"], kept["memory"])
    except (OSError, ValueError, KeyError, zlib.error):
        pass
    compiled = _real_compile(lowered)
    stats = compiled.memory_analysis()
    kept = {"text": compiled.as_text(),
            "memory": {name: getattr(stats, name) for name in _MEMORY}}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    scratch = "%s.%d" % (path, os.getpid())
    with open(scratch, "wb") as f:
        f.write(zlib.compress(json.dumps(kept).encode(), 1))
    os.replace(scratch, path)
    return _Compiled(kept["text"], kept["memory"])


@pytest.fixture(autouse=True)
def traced_afresh():
    """Every test traces its program itself.  The scopes and lines a
    compiled text names come from the locations of the lowered
    operations, which are in the digest; a function traced by an earlier
    test would bring that test's call stack, and the digest would follow
    the schedule."""
    jax.clear_caches()


def test_a_compiled_text_is_kept_by_the_whole_of_the_compilers_input(
        tmp_path, monkeypatch):
    """``_compile_once`` (here on a CPU program: no topology) compiles a
    program it has not seen, hands the same text and memory analysis
    back for one it has, and takes a scope's name, which only the
    locations carry, for another program; a file that is not a kept
    entry is compiled over."""
    import sys

    here = sys.modules[__name__]
    compiles, real = [], _real_compile
    monkeypatch.setattr(here, "_kept_dir", lambda: str(tmp_path))
    monkeypatch.setattr(here, "_real_compile",
                        lambda lowered: compiles.append(1) or real(lowered))

    def program(scope):
        def double(x):
            with jax.named_scope(scope):
                return x * 2

        return jax.jit(double).lower(jnp.ones(4))

    # (jax's own cache leaves names out of its key and would hand the
    # first program's text back for the renamed one)
    with _compile_cache_off():
        passes = []
        for torn in (False, True):  # one call site: the same locations
            if torn:
                (tmp_path / min(os.listdir(tmp_path))).write_bytes(b"torn")
            passes.append([_compile_once(program(scope))
                           for scope in ("one", "one", "other")])
            assert len(compiles) == 2 + torn
    first, again, other = passes[0]
    assert "/one/" in first.as_text() and "/other/" in other.as_text()
    assert first.memory_analysis().argument_size_in_bytes == 16
    for got in [again] + passes[1][:2]:
        assert got.as_text() == first.as_text()
        assert vars(got.memory_analysis()) == vars(first.memory_analysis())
    assert passes[1][2].as_text() == other.as_text()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernel dispatch believes it targets the TPU (compiled Mosaic
    kernels, flash instead of the einsum reference, the paged kernel
    instead of its XLA twin)."""
    import orion_tpu.ops.pallas as pallas

    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")
    assert not pallas.interpret_mode()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernel_names(compiled) -> list:
    """The compiled program's Pallas kernels by the name the profiler's
    device events will carry: the HLO instruction's (``%flash_fwd.3 =
    ... custom-call(...) custom_call_target="tpu_custom_call"`` ->
    ``flash_fwd``), sorted, one entry per call."""
    import re

    return sorted(
        re.sub(r"\.\d+$", "", m.group(1)) for m in re.finditer(
            r"^\s*(?:ROOT )?%([\w.\-]+) = .*custom-call\(.*"
            r'custom_call_target="tpu_custom_call"',
            compiled.as_text(), re.M))


def _state_copies(text: str, shape, moves: bool = False) -> list:
    """The instructions of a compiled program (or of one computation's
    lines) that copy a float32 array of ``shape`` from the HBM to the
    HBM: ``copy``, and a ``copy-start`` neither side of which lies in
    the memory space ``S(1)``.  XLA's memory-space assignment may move
    a buffer into or out of ``S(1)`` around its use (a ``copy-start``
    with ONE side there): a crossing of the HBM that the use then does
    not make, not one more; ``moves`` counts those too."""
    import re

    dims = ",".join(map(str, shape))
    array = r"f32\[%s\]\{[^}]*\}" % dims
    found = []
    for ln in text.splitlines():
        m = re.search(r"= \((%s), (%s), [^)]*\) copy-start\(" % (
            array, array), ln)
        if m:
            if moves or ("S(1)" in m.group(1)) == ("S(1)" in m.group(2)):
                found.append(ln.strip()[:160])
        elif re.search(r"= %s copy\(" % array, ln):
            found.append(ln.strip()[:160])
    return found


def _computations(text: str) -> dict:
    """{computation name: its lines} of a compiled program's text."""
    import re

    comps, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif ln.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(ln)
    return {name: "\n".join(lines) for name, lines in comps.items()}


def _called(comps: dict, name: str) -> set:
    """``name`` and every computation it calls, directly or not."""
    import re

    seen, todo = set(), [name]
    while todo:
        cur = todo.pop()
        if cur in seen or cur not in comps:
            continue
        seen.add(cur)
        todo += re.findall(r"%?([\w.\-]+)", " ".join(re.findall(
            r"(?:calls|to_apply|body|condition|branch_computations)="
            r"\{?([^}\s,]+(?:, *[^}\s,]+)*)", comps[cur])))
    return seen


def _while_bodies(text: str) -> dict:
    """{computation name: its lines} of every ``while`` body of a
    compiled program's text."""
    import re

    comps = _computations(text)
    return {b: comps[b] for b in re.findall(
        r" while\(.*body=%?([\w.\-]+)", text)}


# (B, Lq, Lk, H, Hkv, D, first q position)
FLASH_SHAPES = {
    # the ppo1b update/experience shape (chip_smoke.py, ppo1b-sync)
    "pythia1b": (16, 384, 384, 8, 8, 256, 0),
    # llama3-8B width (GQA 32/8, D=128)
    "llama8b": (4, 1024, 1024, 32, 8, 128, 0),
    # grouped calls: Nemotron-H's attention layer as ppo-nemotron-h-tp4-
    # sync cuts it (8 query heads on ONE key head, 1280 tokens) and
    # Keye-VL-2.0's heads without a selection (32 on 4, 8192 tokens:
    # eight heads' blocks and 1024-wide major blocks in one grid step)
    "nemotron_h": (16, 1280, 1280, 8, 1, 128, 0),
    "keye_dense": (2, 8192, 8192, 32, 4, 128, 0),
    # LFM2's attention layers as ppo-lfm2-ep4-sync runs them: heads of
    # 64 (half a lane tile), four query heads a key head, 1280 tokens
    "lfm2": (16, 1280, 1280, 32, 8, 64, 0),
    # tests/test_tpu_smoke.py regression shapes: a cache length that is
    # no multiple of 128 ...
    "odd_cache_144": (2, 16, 144, 8, 4, 64, 128),
    # ... and the speculative-verify chunk (Lq=5 over Lk=388=4*97)
    "spec_verify_5x388": (4, 5, 388, 8, 8, 64, 300),
}


def _flash_args(name, sharding):
    B, Lq, Lk, H, Hkv, D, _ = FLASH_SHAPES[name]
    return (_sds((B, Lq, H, D), BF16, sharding),
            _sds((B, Lk, Hkv, D), BF16, sharding),
            _sds((B, Lk, Hkv, D), BF16, sharding))


def _flash(name):
    from orion_tpu.ops.pallas.flash_attention import flash_attention_gqa

    B, Lq, _, _, _, D, p0 = FLASH_SHAPES[name]

    def fwd(q, k, v):
        qpos = jnp.broadcast_to(
            jnp.arange(p0, p0 + Lq, dtype=jnp.int32), (B, Lq))
        return flash_attention_gqa(q, k, v, qpos, D ** -0.5)

    return fwd


@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_fwd_compiles_for_v5e(name, one_chip, on_tpu):
    compiled = jax.jit(_flash(name)).lower(
        *_flash_args(name, one_chip)).compile()
    assert _kernel_calls(compiled) == 1
    assert _kernel_names(compiled) == ["flash_fwd"]


def _group_vmem_asked(compiled) -> dict:
    """{kernel name: the scoped VMEM its custom call asks for, bytes}
    of the compiled program's Pallas kernels (0 where a call asks for
    nothing: Mosaic's default limit)."""
    import re

    asked = {}
    for ln in compiled.as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w\-]+?)(?:\.\d+)? = .*custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', ln)
        if m:
            size = re.search(
                r'"scoped_memory_configs":\[\{[^]]*"size":"(\d+)"', ln)
            asked[m.group(1)] = int(size.group(1)) if size else 0
    return asked


def _assert_group_gradients_leave_summed(compiled, name):
    """``flash_bwd_dkv`` / ``sparse_bwd_dkv`` of a call whose query
    heads share key heads write dK and dV of the KEY heads in the
    inputs' dtype: no float32 gradient a query head, and no reduce over
    a group behind the kernel.  A call of one query head a key head asks
    for no VMEM beyond the default; a group's kernels ask for what their
    blocks take and compile under it, far below the chip's 128 MiB."""
    import re

    B, Lq, Lk, H, Hkv, D, _ = FLASH_SHAPES[name]
    text = compiled.as_text()
    assert f"f32[{B},{H},{Lk},{D}]" not in text
    assert not re.search(
        r"= \w+\[%d,(%d,%d|%d,%d),%d\]\S* reduce\(" % (
            B, Hkv, Lk, Lk, Hkv, D), text)
    asked = _group_vmem_asked(compiled)
    assert len(asked) == 3
    if H == Hkv:
        assert set(asked.values()) == {0}
    else:
        assert all(16 << 20 < a <= 48 << 20 for a in asked.values()), asked
        assert re.search(r"bf16\[%d,%d,%d,%d\]\S*, bf16\[%d,%d,%d,%d\]\S*\) "
                         r"custom-call" % ((B, Hkv, Lk, D) * 2), text)


@pytest.mark.parametrize("name", ["pythia1b", "llama8b", "nemotron_h",
                                  "keye_dense", "lfm2"])
def test_flash_fwd_bwd_compiles_for_v5e(name, one_chip, on_tpu):
    fwd = _flash(name)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_flash_args(name, one_chip)).compile()
    # forward + dq + dkv kernels, each under its own name
    assert _kernel_calls(compiled) == 3
    assert _kernel_names(compiled) == ["flash_bwd_dkv", "flash_bwd_dq",
                                       "flash_fwd"]
    _assert_group_gradients_leave_summed(compiled, name)


def test_flash_ring_chunk_compiles_for_v5e(one_chip, on_tpu):
    """The ring path's per-chunk entries (explicit kv positions: a
    lane-major position block in forward and dq, a sublane-major one in
    the transposed dkv tiles) at a 1024-token chunk of llama-3-8B
    heads, bf16."""
    from orion_tpu.ops.pallas.flash_attention import (flash_chunk_fwd,
                                                      flash_chunk_grads)

    B, L, H, Hkv, D = 2, 1024, 32, 8, 128

    def chunk(q, k, v, dout, qpos, kvpos):
        out, lse = flash_chunk_fwd(q, k, v, qpos, kvpos, D ** -0.5)
        return flash_chunk_grads(q, k, v, qpos, kvpos, out, lse, dout,
                                 D ** -0.5)

    compiled = jax.jit(chunk).lower(
        _sds((B, L, H, D), BF16, one_chip),
        _sds((B, L, Hkv, D), BF16, one_chip),
        _sds((B, L, Hkv, D), BF16, one_chip),
        _sds((B, L, H, D), BF16, one_chip),
        _sds((B, L), jnp.int32, one_chip),
        _sds((B, L), jnp.int32, one_chip)).compile()
    assert _kernel_names(compiled) == ["flash_bwd_dkv", "flash_bwd_dq",
                                       "flash_fwd"]


# The serving shape: B=48 slots, page_size 64, 288 pages (+1 scratch).
PAGED = dict(B=48, pages=289, page_size=64, max_pages=6)
PAGED_WIDTHS = {"pythia1b": (8, 8, 256), "llama8b": (32, 8, 128)}


def _paged_args(width, quantized, shard):
    """Abstract paged-decode operands; ``shard(kind)`` gives the
    sharding for 'q' / 'pool' / 'rep'."""
    H, Hkv, D = PAGED_WIDTHS[width]
    B, N, ps, mp = (PAGED["B"], PAGED["pages"], PAGED["page_size"],
                    PAGED["max_pages"])
    pool_dt = jnp.int8 if quantized else BF16
    args = [_sds((B, H, D), BF16, shard("q")),
            _sds((N, Hkv, ps, D), pool_dt, shard("pool")),
            _sds((N, Hkv, ps, D), pool_dt, shard("pool"))]
    if quantized:
        args += [_sds((N, Hkv, 1, ps), jnp.float32, shard("pool")),
                 _sds((N, Hkv, 1, ps), jnp.float32, shard("pool"))]
    args += [_sds((B, mp), jnp.int32, shard("rep")),
             _sds((B,), jnp.int32, shard("rep"))]
    return args, D ** -0.5


def _paged_fn(entry, quantized, scale):
    def fn(q, kp, vp, *rest):
        if quantized:
            ks, vs, bt, ln = rest
        else:
            (bt, ln), ks, vs = rest, None, None
        return entry(q, kp, vp, bt, ln, scale, k_scales=ks, v_scales=vs)

    return fn


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("width", sorted(PAGED_WIDTHS))
def test_paged_decode_compiles_for_v5e(width, quantized, one_chip,
                                       on_tpu):
    from orion_tpu.ops.pallas.paged_attention import paged_decode_attention

    args, scale = _paged_args(width, quantized, lambda kind: one_chip)
    compiled = jax.jit(_paged_fn(paged_decode_attention, quantized,
                                 scale)).lower(*args).compile()
    assert _kernel_calls(compiled) == 1
    assert _kernel_names(compiled) == ["paged_decode"]


@pytest.mark.parametrize("quantized", [False, True],
                         ids=["bf16", "int8"])
def test_paged_decode_sharded_compiles_for_2x2(quantized, topo):
    """The tensor-parallel decode wraps the kernel in a partial-manual
    ``shard_map`` (manual over 'tensor', auto over 'fsdp'); this native
    lowering has never met the TPU compiler before.  The kernel must be
    in the per-device program and the KV pool must NOT be all-gathered
    (the pool stays kv-head-sharded; q and the output are tiny)."""
    from orion_tpu.ops.pallas.paged_attention import (
        paged_decode_attention_sharded)

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2),
                ("fsdp", "tensor"))
    spec = {"q": P(None, "tensor", None),
            "pool": P(None, "tensor", None, None), "rep": P()}
    args, scale = _paged_args(
        "pythia1b", quantized,
        lambda kind: NamedSharding(mesh, spec[kind]))
    # no monkeypatch: the mesh's devices ARE tpu devices, and
    # target_platform() reads the mesh context
    with mesh:
        compiled = jax.jit(_paged_fn(paged_decode_attention_sharded,
                                     quantized, scale)).lower(
                                         *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert _kernel_names(compiled) == ["paged_decode"]
    H, Hkv, D = PAGED_WIDTHS["pythia1b"]
    pool = f"[{PAGED['pages']},{Hkv},{PAGED['page_size']},{D}]"
    gathers = [ln for ln in text.splitlines() if "all-gather" in ln]
    assert not any(pool in ln for ln in gathers), gathers


def test_pythia1b_ppo_update_compiles_for_2x2(topo):
    """The whole shared-backbone PPO update at Pythia-1B (16 layers,
    remat, scanned) over a described fsdp=2 x tensor=2 mesh with the
    real param shardings: the SPMD partitioner runs, and the flash
    kernels are in the per-device program.  Before PR 22 this did not
    lower at all — jax refuses a Mosaic kernel in an automatically
    partitioned program; ``ops.attention._flash_on_mesh`` wraps it."""
    from orion_tpu.config import MeshConfig, ModelConfig
    from orion_tpu.parallel.mesh import make_mesh
    from orion_tpu.utils.compile_check import lower_8b_update

    mesh = make_mesh(MeshConfig(data=1, fsdp=2, tensor=2),
                     devices=topo.devices)
    with mesh:   # target_platform() reads the mesh: its devices are tpu
        status = lower_8b_update(mesh=mesh, compile=True,
                                 model_cfg=ModelConfig.pythia_1b())
    assert status.startswith("ok (1.01B params compiled"), status


def test_pythia1b_decode_segment_compiles_for_v5e(one_chip, on_tpu):
    """The whole decode-segment program of the continuous engine at the
    Pythia-1B serving shape (16 layers, int8 weights + KV, 32 slots,
    page_size 64): abstract arguments shaped like the engine's own,
    compiled for one described chip.  Also says whether it fits HBM."""
    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.models.transformer import prep_decode_params
    from orion_tpu.rollout.continuous import ContinuousBatchingEngine

    mc = ModelConfig.pythia_1b()
    rc = RolloutConfig(max_prompt_len=512, max_new_tokens=128,
                       page_size=64, max_batch_size=32,
                       quantize_weights=True, quantize_kv=True,
                       engine="continuous")
    model = Transformer(mc)
    eng = ContinuousBatchingEngine(model, mc, rc, eos_token_id=0,
                                   pad_token_id=0)

    def abstract(tree):
        return jax.tree.map(
            lambda x: _sds(x.shape, x.dtype, one_chip), tree)

    params = jax.eval_shape(lambda: prep_decode_params(
        init_params(model, jax.random.key(0), mc), mc, True))
    rng = jax.eval_shape(lambda: jax.random.key(0))
    compiled = eng._jit_segment.lower(
        abstract(params), abstract(eng._pools),
        _sds(eng._bt.shape, jnp.int32, one_chip),
        abstract(jax.eval_shape(eng._init_state)),
        _sds(rng.shape, rng.dtype, one_chip),
        n_steps=eng.segment_len).compile()
    # one paged-decode kernel call per layer program (layers unrolled
    # or scanned — at least one call either way)
    assert _kernel_calls(compiled) >= 1
    names = _kernel_names(compiled)
    assert names and set(names) == {"paged_decode"}, names
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < 16e9, f"decode segment needs {total / 1e9:.1f} GB"


@pytest.mark.parametrize("rows,keep,forwards", [
    (16, (), 2), (16, ("attn_out",), 1), (4, "every tag", 1)],
    ids=["nothing-kept", "attn_out-kept", "everything-kept-4-rows"])
def test_pythia1b_update_keeps_the_flash_forward(one_chip, on_tpu, rows,
                                                 keep, forwards):
    """The ``ppo1b-sync`` update (minibatches of 16 x 384, one chip)
    under ``model.remat``: with nothing kept the scanned block's
    backward runs the flash forward a second time; with the kernel's
    output among the kept tags (``attn_out``) there is one forward
    call a layer pass, and the program fits the chip.  Everything kept
    does not fit beside the update's own 5.8 GB at 16 rows (PERF.md
    section 6, PR 31), so that case runs a quarter of the minibatch."""
    from orion_tpu.config import ModelConfig
    from orion_tpu.models.transformer import REMAT_TAGS
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc = ModelConfig.pythia_1b()
    shell, pshape, mb = _build_8b_shell(mc)
    shell._remat_keep = REMAT_TAGS if keep == "every tag" else keep
    experience = {k: _sds((3 * rows,) + v.shape[1:], v.dtype, one_chip)
                  for k, v in mb.items()}
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         _abstract_state(shell, pshape))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
            donate_argnums=(0,)).lower(
                state, experience, _sds((3, rows), jnp.int32, one_chip)
            ).compile()
    names = _kernel_names(compiled)
    assert names.count("flash_fwd") == forwards, names
    assert names.count("flash_bwd_dq") == names.count("flash_bwd_dkv") == 1
    assert compiled.memory_analysis().peak_memory_in_bytes <= 15.75 * 2**30


# -- the deepseek_v3 block's kernels at the published widths ----------------

def test_flash_with_a_narrower_value_compiles_for_v5e(one_chip, on_tpu):
    """Latent attention expands keys of 192 (128 + 64 rotary) and values
    of 128: forward and both backward kernels at the update's shape of
    ``ppo-kanana-ep8-sync`` (16 x 1024, 32 heads)."""
    from orion_tpu.ops.pallas.flash_attention import flash_attention_gqa

    B, L, H, D, Dv = 16, 1024, 32, 192, 128

    def loss(q, k, v):
        qpos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        out = flash_attention_gqa(q, k, v, qpos, D ** -0.5)
        assert out.shape == (B, L, H, Dv)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _sds((B, L, H, D), BF16, one_chip),
        _sds((B, L, H, D), BF16, one_chip),
        _sds((B, L, H, Dv), BF16, one_chip)).compile()
    assert _kernel_names(compiled) == ["flash_bwd_dkv", "flash_bwd_dq",
                                       "flash_fwd"]


# cell -> (rows a decode step, hidden or latent, expert width, held,
# top-k, activation): the small step's expert layer where the rule takes
# the kernel (ops/moe.py::step_form; tests/test_experts_step.py has the
# rule's table)
STEP_SHAPES = {
    "keye-16of128-top8-8rows": (8, 2048, 768, 16, 8, "swiglu"),
    "mellum2-8of64-top8-8rows": (8, 2304, 896, 8, 8, "swiglu"),
    "kimi-8of256-top8-32rows": (32, 2304, 1024, 8, 8, "swiglu"),
    "kanana-16of128-top6-32rows": (32, 2048, 768, 16, 6, "swiglu"),
    "nemotron-8of512-top22-32rows": (32, 1024, 2688, 8, 22, "relu2"),
    # every stack hit: the kernel for its rate alone (PR 57)
    "lfm2-8of32-top4-64rows": (64, 2048, 1792, 8, 4, "swiglu"),
}


@pytest.mark.parametrize("name", sorted(STEP_SHAPES))
def test_experts_step_compiles_for_v5e(name, one_chip, on_tpu):
    """The kernel alone at each cell's decode shapes: one Mosaic call
    under its name, tiles of whole lanes, inside the VMEM it asks for."""
    from orion_tpu.ops.moe import ACTIVATIONS
    from orion_tpu.ops.pallas import experts_step as es

    T, D, I, H, k, act = STEP_SHAPES[name]
    assert es.width_tile(I) % 128 == 0 and I % es.width_tile(I) == 0
    compiled = jax.jit(lambda *a: es.experts_step(*a, act)).lower(
        _sds((T, D), BF16, one_chip),
        _sds((H, D, ACTIVATIONS[act][1] * I), BF16, one_chip),
        _sds((H, I, D), BF16, one_chip), _sds((T, k), jnp.int32, one_chip),
        _sds((T, k), jnp.float32, one_chip)).compile()
    assert _kernel_names(compiled) == ["experts_step"]


def _assert_step_experts(compiled, form: str, layers: int, product: str):
    """The decode loop's expert layers: under the kernel form one
    ``experts_step`` a layer and the einsum form's first product
    (``product``: ``bf16[T, H, F]``) nowhere; under the einsum form that
    product, and no such kernel."""
    names, text = _kernel_names(compiled), compiled.as_text()
    assert names.count("experts_step") == (layers if form == "kernel" else 0)
    assert (product in text) == (form != "kernel")


# (T, hidden, expert width, held, top-k, experts, rows of a block, the
# same function's ``temp_size_in_bytes`` at PR 34, where every pair had
# a row): the update's minibatch of the two expert cells
GROUPED_SHAPES = {
    "kanana-16of128-top6": (16 * 1024, 2048, 768, 16, 6, 128, 24576,
                            2_551_555_584),
    "kimi-8of256-top8": (16 * 1024, 2304, 1024, 8, 8, 256, 8192,
                         2_551_714_816),
}


@pytest.mark.parametrize("name", sorted(GROUPED_SHAPES))
def test_grouped_expert_product_compiles_for_v5e(name, one_chip, on_tpu):
    """The dropless expert path at the update's shape of
    ``ppo-kanana-ep8-sync`` (16 x 1024 tokens, top-6 of 128, 16 experts
    held, hidden 2048, expert width 768) and of
    ``ppo-kimi-linear-ep32-sync`` (top-8 of 256, 8 held, 2304, 1024):
    the grouped product and its two backward halves by the names the
    device trace will carry, over a block of the held pairs: nothing in
    the compiled program has a row for every (token, choice) pair."""
    import re
    import types

    from orion_tpu.ops.moe import block_rows, experts_grouped

    T, D, I, H, k, E, rows, temp_before = GROUPED_SHAPES[name]
    block = block_rows(types.SimpleNamespace(
        num_experts_per_tok=k, experts_held=H, n_routed_experts=E), T)
    assert block == rows == 2 * T * k * H // E

    def loss(x, w_gate_up, w_down, local, gates):
        return jnp.sum(experts_grouped(x, w_gate_up, w_down, local, gates,
                                       block).astype(jnp.float32))

    # the suite's "highest" matmul precision is for float32 parity on
    # the CPU; Mosaic refuses it on the kernels' bfloat16 operands, and
    # no program sets it
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 4))).lower(
            _sds((T, D), BF16, one_chip),
            _sds((H, D, 2 * I), BF16, one_chip),
            _sds((H, I, D), BF16, one_chip),
            _sds((T, k), jnp.int32, one_chip),
            _sds((T, k), jnp.float32, one_chip)).compile()
    names = _kernel_names(compiled)
    assert set(names) == {"moe_gmm", "moe_gmm_dlhs", "moe_tgmm",
                          "moe_combine"}, names
    # The written backward of one block: the gate|up product again (the
    # activation is rebuilt, not kept), the gradient of the rows through
    # both products and both weight gradients; the forward's own two
    # products are dead code for these gradients.  Each stands ONCE:
    # every block, the first too, is a trip of the same loop.
    assert names.count("moe_gmm") == 1
    assert names.count("moe_gmm_dlhs") == 2 and names.count("moe_tgmm") == 2
    # ... and so does the combine kernel: the backward's own, which
    # places the rows' gradients into ``d_x`` without a gate; the
    # forward's, with its gates, is dead code here like its products
    assert names.count("moe_combine") == 1
    # no row scatter-add into a float32 [T, D] is left (the sum's tile
    # stays in VMEM and the rows come to it: ops/pallas/moe_combine.py);
    # the scatter of the gates' gradients is one of scalars
    scatters = [line for line in compiled.as_text().splitlines()
                if re.search(r"= f32\[%d,%d\]\S* scatter\(" % (T, D), line)]
    assert not scatters, scatters
    # rows are gathered, multiplied and summed a block at a time: what
    # still has T * k entries are vectors (sort keys, order, gates)
    wide = re.findall(r"= \w+\[%d,(\d+)" % (T * k), compiled.as_text())
    assert all(int(cols) == 1 for cols in wide), wide
    # scratch of the compiled program: 843 421 184 (Kanana) and
    # 609 576 448 bytes (Kimi) at PR 35, a third and a quarter of PR 34's
    assert compiled.memory_analysis().temp_size_in_bytes < temp_before // 2


# -- the kimi_linear block at the published widths --------------------------

@pytest.mark.parametrize("H,dk,dv,one_decay", [
    (32, 128, 128, False), (30, 96, 192, True)],
    ids=["kimi-32x128x128", "olmo-30x96x192"])
def test_delta_rule_compiles_for_v5e(one_chip, on_tpu, H, dk, dv, one_decay):
    """Both forms of the delta rule at the shapes of
    ``ppo-kimi-linear-ep32-sync`` (32 heads of 128, a decay a channel)
    and of ``ppo-olmo-hybrid-vp8-sync`` (30 heads of 96 x 192, one decay
    a head: padded to 128 x 256 around the call): the chunked form with
    its backward at a minibatch of 16 x 1024 (the two kernels of
    ops/pallas/kda_chunk.py, through Mosaic) and one decode step over a
    batch of 32: exactly one ``kda_step`` kernel (ops/pallas/
    kda_step.py, at the heads' own sizes: nothing padded), its state
    operand aliased to its state result."""
    import re

    from orion_tpu.ops.kda import (chunk_form, kda_chunked, kda_step,
                                   step_form)

    B, L = 16, 1024
    assert chunk_form(dk, dv) == step_form(dk, dv) == "kernel"
    gd = 1 if one_decay else dk

    def loss(q, k, v, g, beta):
        o, S = kda_chunked(q, k, v, g, beta)
        assert o.shape == (B, L, H, dv) and S.shape == (B, H, dk, dv)
        return jnp.sum(o) + jnp.sum(S)

    with jax.default_matmul_precision("default"):
        chunked = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            *(_sds((B, L, H, dk), BF16, one_chip),) * 2,
            _sds((B, L, H, dv), BF16, one_chip),
            _sds((B, L, H, gd), jnp.float32, one_chip),
            _sds((B, L, H), jnp.float32, one_chip)).compile()
        step = jax.jit(kda_step, donate_argnums=(5,)).lower(
            *(_sds((32, H, dk), BF16, one_chip),) * 2,
            _sds((32, H, dv), BF16, one_chip),
            _sds((32, H, gd), jnp.float32, one_chip),
            _sds((32, H), jnp.float32, one_chip),
            _sds((32, H, dk, dv), jnp.float32, one_chip)).compile()
    assert _kernel_names(chunked) == ["kda_chunk_bwd", "kda_chunk_fwd"]
    assert _kernel_names(step) == ["kda_step"]
    assert _kernel_calls(step) == 1
    text = step.as_text()
    # the kernel writes the new state into the old one's buffer, the
    # program hands the donated argument's buffer back, and nothing
    # copies a state on the way
    assert re.search(r"%kda_step[.\d]* = .*output_to_operand_aliasing="
                     r"\{\{1\}: \(6, \{\}\)\}", text), text[-3000:]
    assert re.search(r"input_output_alias=\{[^\n]*\(5, \{\}", text)
    assert not _state_copies(text, (32, H, dk, dv))
    # what the backward holds of one layer: the states at the 16 chunk
    # boundaries and the inputs, not the chunks' insides
    assert chunked.memory_analysis().peak_memory_in_bytes < 4 * 2**30


_DELTA_ENGINES: dict = {}


def _delta_engine(name, one_chip):
    """(compiled text, kernel names, state shape) of the fixed-batch
    engine's whole program (prefill + the decode ``while_loop``) of
    ``ppo-kimi-linear-ep32-sync`` (three of its layers: two KDA, one
    latent) or ``ppo-olmo-hybrid-vp8-sync`` (one period: three GDN, one
    full attention) at 32 x 512 + 512, compiled once a process (the
    caller holds ``on_tpu``)."""
    import dataclasses

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.rollout.engine import RolloutEngine

    if name in _DELTA_ENGINES:
        return _DELTA_ENGINES[name]
    if name == "kimi_linear":
        mc = dataclasses.replace(
            ModelConfig.kimi_linear_48b_a3b(), num_layers=3,
            kda_layers=(1, 2), experts_held=8, vocab_size=20480,
            max_seq_len=1024)
    else:
        mc = dataclasses.replace(ModelConfig.olmo_hybrid_7b(), num_layers=4,
                                 vocab_size=12544, max_seq_len=1024)
    H = mc.kda_num_heads if name == "kimi_linear" \
        else mc.linear_num_key_heads
    state_shape = (32, H) + tuple(mc.delta_head_dims())
    model = Transformer(mc)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: init_params(model, jax.random.key(0), mc)))
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=512, max_new_tokens=512), eos_token_id=0,
        pad_token_id=0)
    states = len([m for m, _ in mc.layer_kinds() if m in ("kda", "gdn")])
    assert eng.dispatch_attrs((32, 512), [512] * 32)["state_bytes"] \
        >= states * 4 * int(np.prod(state_shape))
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with jax.default_matmul_precision("default"):
        compiled = eng._generate_jit.lower(
            params, _sds((32, 512), jnp.int32, one_chip),
            _sds((32,), jnp.int32, one_chip),
            _sds(rng.shape, rng.dtype, one_chip),
            max_new_tokens=512).compile()
    _DELTA_ENGINES[name] = (compiled.as_text(), _kernel_names(compiled),
                            state_shape)
    return _DELTA_ENGINES[name]


@pytest.mark.parametrize("name,states", [("kimi_linear", 2),
                                         ("olmo_hybrid", 3)])
def test_delta_rule_decode_carries_one_state_buffer_a_layer(
        one_chip, on_tpu, name, states):
    """The decode loop's body holds one ``kda_step`` kernel a recurrent
    layer, each writing its state into the buffer it read (the loop
    carries one buffer a layer), and no copy of a whole state from the
    HBM to the HBM."""
    import re

    text, kernels, state_shape = _delta_engine(name, one_chip)
    assert kernels.count("kda_step") == states
    decode = [body for body in _while_bodies(text).values()
              if "%kda_step" in body]
    assert len(decode) == 1                     # the decode loop's body
    steps = re.findall(r"%kda_step[.\d]* = [^\n]*", decode[0])
    assert len(steps) == states
    for call in steps:
        assert "f32[%s]" % ",".join(map(str, state_shape)) in call
        assert "output_to_operand_aliasing={{1}: (6, {})}" in call
    # no copy of a state; at Olmo-Hybrid's three states of 94 MB (as the
    # HBM's tiles hold 96 x 192) XLA's memory-space assignment moves
    # states into and out of ``S(1)`` around the kernels, as it did
    # around the ``jax.numpy`` step: Kimi's loop has none of those either
    assert not _state_copies(decode[0], state_shape)
    if name == "kimi_linear":
        assert not _state_copies(decode[0], state_shape, moves=True)


@pytest.mark.parametrize("name,cache", [
    ("olmo_hybrid", "bf16[32,1024,30,128]"),
    ("kimi_linear", "bf16[32,1024,512]")])
def test_decode_step_switches_over_prefixes_of_the_cache_in_place(
        one_chip, on_tpu, name, cache):
    """The same programs' decode loop: one ``conditional`` of 8 branches
    a layer with a slot cache (Olmo-Hybrid's full-attention layer,
    Kimi's latent layer); the cache reaches it as the loop carries it
    (no ``copy`` / ``copy-start`` of a cache-shaped buffer in the body
    or in a branch), and no branch makes a standalone ``slice`` or
    ``copy`` of a prefix of the per-head cache: the fusions slice their
    operand themselves (``ops/attention.py::step_attention``)."""
    import re

    text, _, _ = _delta_engine(name, one_chip)
    comps = _computations(text)
    (decode,) = [body for body in _while_bodies(text).values()
                 if "%kda_step" in body]
    found = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                       decode)
    assert len(found) == 1
    branches = [b.strip().lstrip("%") for b in found[0].split(",")]
    assert len(branches) == 8
    whole = re.escape(cache)
    for where in [decode] + [comps[b] for b in branches]:
        assert not re.search(r"= %s\S* copy(-start)?\(" % whole, where)
    if name == "olmo_hybrid":
        for b in branches:                  # bf16[32,<prefix>,30,128]
            assert not re.search(
                r"= bf16\[32,\d+,30,128\]\S* (slice|copy)\(", comps[b])
            assert "multiply_reduce_fusion" in comps[b]


def test_kanana_decode_loop_copies_no_whole_latent_cache(one_chip, on_tpu):
    """The fixed-batch engine's whole program of ``ppo-kanana-ep8-sync``
    (all six layers: the dense one and five of 16 held experts, latent
    attention in each) at 32 x 512 + 512: the decode loop holds one
    ``conditional`` of 8 branches a layer, and neither its body nor a
    branch copies a whole layer's latents (``bf16[32,1024,512]``; no
    ``copy``, ``copy-start`` or ``copy-done``).  This pins what the
    compile for a described chip shows, and that is less than the chip
    showed: the cell's trace of a first written form of this step had
    the whole ``c`` staged through ``S(1)``, this tree's has not, and
    what could be rebuilt of the difference (the step behind a
    ``jax.jit`` or inline) leaves this text the same (PERF.md section
    7, PR 43)."""
    import dataclasses
    import re

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.rollout.engine import RolloutEngine

    mc = dataclasses.replace(
        ModelConfig.kanana_2_30b_a3b(), num_layers=6, experts_held=16,
        vocab_size=16032, max_seq_len=1024, scan_layers=True)
    model = Transformer(mc)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: init_params(model, jax.random.key(0), mc)))
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=512, max_new_tokens=512), eos_token_id=0,
        pad_token_id=0)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with jax.default_matmul_precision("default"):
        text = eng._generate_jit.lower(
            params, _sds((32, 512), jnp.int32, one_chip),
            _sds((32,), jnp.int32, one_chip),
            _sds(rng.shape, rng.dtype, one_chip),
            max_new_tokens=512).compile().as_text()
    comps = _computations(text)
    switch = r" conditional\(.*branch_computations=\{([^}]*)\}"
    (decode,) = [body for body in _while_bodies(text).values()
                 if re.search(switch, body)
                 and re.search(switch, body).group(1).count(",") == 7]
    found = re.findall(switch, decode)
    assert [f.count(",") + 1 for f in found] == [8] * 6
    branches = [b.strip().lstrip("%") for f in found for b in f.split(",")]
    for where in [decode] + [comps[b] for b in branches]:
        assert not re.search(
            r"= bf16\[32,1024,512\]\S* copy(-start|-done)?\(", where)


def test_kimi_linear_update_compiles_for_v5e(one_chip, on_tpu):
    """The shared-backbone PPO update over every kind of layer the
    ``kimi_linear`` pattern has, at the published widths: a dense KDA
    layer, a KDA layer and a latent layer without rotation over the
    expert layer (8 of 256 held), remat, each stretch scanned.  The
    update has the flash kernels (the latent layer), the grouped
    products (the experts) and the chunked delta rule's two kernels (the
    KDA layers) in it and fits the chip."""
    import dataclasses

    from orion_tpu.config import ModelConfig
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc = dataclasses.replace(
        ModelConfig.kimi_linear_48b_a3b(), num_layers=3, kda_layers=(1, 2),
        experts_held=8, vocab_size=20480,
        max_seq_len=1024)
    assert mc.layer_runs() == ((0, 1, "kda", "dense"),
                               (1, 1, "kda", "experts"),
                               (2, 1, "latent", "experts"))
    shell, pshape, mb = _build_8b_shell(mc)
    rows, S, T = 4, 1024, 512
    shell.cfg.rollout.max_prompt_len = shell.cfg.rollout.max_new_tokens = T
    shapes = {k: (S,) if k == "sequences" else () if k == "prompt_lens"
              else (T,) for k in mb}
    experience = {k: _sds((2 * rows,) + shapes[k], v.dtype, one_chip)
                  for k, v in mb.items()}
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         _abstract_state(shell, pshape))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
            donate_argnums=(0,)).lower(
                state, experience, _sds((2, rows), jnp.int32, one_chip)
            ).compile()
    assert _kernel_calls(compiled) >= 1          # tpu_custom_call
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "moe_gmm",
            "moe_gmm_dlhs", "moe_tgmm", "moe_combine", "kda_chunk_fwd",
            "kda_chunk_bwd"} <= set(_kernel_names(compiled))
    assert compiled.memory_analysis().peak_memory_in_bytes <= 15.75 * 2**30


# -- the olmo_hybrid block at the published widths ---------------------------

#: what the chip holds beside the update's arguments when the update of
#: ``ppo-olmo-hybrid-vp8-sync`` loads (the bf16 reference, the batch):
#: ``bytes_in_use`` 9.339 GB less the 7.431 GB of parameters and moments
#: (my chip run, PR 34); and what the device reports as its limit
OLMO_RESIDENT_BESIDE_ARGS = 9.339e9 - 7.431e9
V5E_BYTES_LIMIT = 16.909e9


@pytest.mark.parametrize("rows,fits", [(4, "chip"), (8, "compiler"),
                                       (16, "nowhere")],
                         ids=["minibatch-4", "minibatch-8", "minibatch-16"])
def test_olmo_hybrid_update_compiles_for_v5e(one_chip, on_tpu, rows, fits):
    """The shared-backbone PPO update of ``ppo-olmo-hybrid-vp8-sync``
    (one period of Olmo-Hybrid-7B at the published widths, 12 544 rows
    of the vocabulary, remat, each stretch scanned) over 32 sequences of
    1024 in minibatches of ``rows``.  The update has the chunked delta
    rule's two kernels (the three GDN layers, heads of 96 x 192 padded
    to the kernels' tiles) and the flash kernels (the full-attention
    layer, 30 heads of 128) in it.  At 16 rows the compiler refuses it
    for a chip of 15.75 GiB (17.8 needed: 6.9 of parameters and
    moments, 10.9 of gradients and activations).  At 8 rows it compiles
    (14.1 GiB) but what it needs beside its arguments does not fit
    beside what else the chip holds then (on the chip: "Attempting to
    reserve 7.34G ... 7.10G free"), which is why the cell runs the
    job's ``-mb4`` copy; at 4 rows it fits with room."""
    import dataclasses

    from orion_tpu.config import ModelConfig
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc = dataclasses.replace(ModelConfig.olmo_hybrid_7b(), num_layers=4,
                             vocab_size=12544, max_seq_len=1024)
    assert mc.layer_runs() == ((0, 3, "gdn", "dense"),
                               (3, 1, "attention", "dense"))
    shell, pshape, mb = _build_8b_shell(mc)
    S, T = 1024, 512
    shell.cfg.rollout.max_prompt_len = shell.cfg.rollout.max_new_tokens = T
    shapes = {k: (S,) if k == "sequences" else () if k == "prompt_lens"
              else (T,) for k in mb}
    experience = {k: _sds((32,) + shapes[k], v.dtype, one_chip)
                  for k, v in mb.items()}
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         _abstract_state(shell, pshape))
    with jax.default_matmul_precision("default"):
        lowered = jax.jit(
            lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
            donate_argnums=(0,)).lower(
                state, experience,
                _sds((32 // rows, rows), jnp.int32, one_chip))
        if fits == "nowhere":
            with pytest.raises(Exception, match="Ran out of memory in "
                                                "memory space hbm"):
                lowered.compile()
            return
        compiled = lowered.compile()
    names = _kernel_names(compiled)
    # the scanned GDN stack: forward, remat's forward, backward
    assert names.count("kda_chunk_fwd") == 2
    assert names.count("kda_chunk_bwd") == 1
    assert names.count("flash_fwd") == 2
    assert names.count("flash_bwd_dq") == names.count("flash_bwd_dkv") == 1
    mem = compiled.memory_analysis()
    assert mem.peak_memory_in_bytes <= 15.75 * 2**30
    # the parameters and their moments: 928.9 M x (4 + 2 + 2) bytes
    assert mem.argument_size_in_bytes == pytest.approx(7.43e9, rel=5e-3)
    on_chip = (mem.peak_memory_in_bytes + OLMO_RESIDENT_BESIDE_ARGS
               <= V5E_BYTES_LIMIT)
    assert on_chip == (fits == "chip")


# -- learned sparse attention (keye_dsa) at the published widths -------------

def _sparse_args(B, Lq, Lk, sharding):
    return (_sds((B, Lq, 32, 128), BF16, sharding),
            _sds((B, Lk, 4, 128), BF16, sharding),
            _sds((B, Lk, 4, 128), BF16, sharding),
            _sds((B, Lk, Lq), jnp.int8, sharding))


@pytest.mark.parametrize("Lq", [8192, 7680], ids=["whole", "prefill"])
def test_selection_kernel_compiles_for_v5e(Lq, one_chip, on_tpu):
    """``dsa_select`` at Keye-VL-2.0's indexer (16 heads of 64, one key
    head, top 2048) over 8192 slots: whole sequences, and the prefill's
    7680 queries against the whole cache.  A tile's [8192, 128] ordered
    scores (4 MiB) live in VMEM beside the keys and the int8 output."""
    from orion_tpu.ops.indexer import select_kernel

    def fn(qi, ki, w):
        pos = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (2, Lq))
        return select_kernel(qi, ki, w, pos, 2048)

    compiled = jax.jit(fn).lower(
        _sds((2, Lq, 16, 64), BF16, one_chip),
        _sds((2, 8192, 64), BF16, one_chip),
        _sds((2, Lq, 16), jnp.float32, one_chip)).compile()
    assert _kernel_names(compiled) == ["dsa_select"]


@pytest.mark.parametrize("Lq", [8192, 7680], ids=["whole", "prefill"])
def test_sparse_attention_compiles_for_v5e(Lq, one_chip, on_tpu):
    """The flash kernels with the selection as an operand (GQA 32 / 4 of
    128, an int8 [keys, queries] block of 1024 x 1024 a grid step beside
    q, k and v), forward and both backward, under their own names."""
    from orion_tpu.ops.pallas.flash_attention import sparse_attention_gqa

    def loss(q, k, v, sel_t):
        pos = jnp.broadcast_to(jnp.arange(Lq, dtype=jnp.int32), (2, Lq))
        return jnp.sum(sparse_attention_gqa(q, k, v, pos, sel_t, 128 ** -0.5
                                            ).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_sparse_args(2, Lq, 8192, one_chip)).compile()
    assert _kernel_names(compiled) == ["sparse_bwd_dkv", "sparse_bwd_dq",
                                       "sparse_fwd"]
    # a grid step holds the 8 query heads of a key head: the kernels ask
    # for the VMEM of their blocks (q, o^T, dO, dq^T 2 MiB each, double
    # buffered, the mask and score tiles), and dK / dV leave summed
    asked = _group_vmem_asked(compiled)
    assert all(16 << 20 < a <= 48 << 20 for a in asked.values()), asked
    text = compiled.as_text()
    assert "f32[2,32,8192,128]" not in text
    assert "bf16[2,4,8192,128]" in text
    import re
    assert not re.search(r"= \w+\[2,(4,8192|8192,4),128\]\S* reduce\(", text)


def test_keye_dsa_update_compiles_for_v5e(one_chip, on_tpu):
    """The shared-backbone PPO update of ``ppo-keye-dsa-ep8-sync`` (6 of
    Keye-VL-2.0's 48 layers at the published widths, 16 of 128 experts,
    18 992 rows of the vocabulary, remat, one scanned stack) over 8
    sequences of 8192 in minibatches of 2.  The update has the selection
    kernel and the selection-taking flash kernels in it (forward and
    remat's forward; one backward each), the grouped products of the
    experts, NONE of the dense flash kernels, and fits the chip: 5.27 GB
    of parameters and moments (659.2 M x 8 bytes), 10.7 GiB at its
    peak."""
    import dataclasses

    from orion_tpu.config import ModelConfig
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc = dataclasses.replace(ModelConfig.keye_vl2_30b_a3b(), num_layers=6,
                             experts_held=16, vocab_size=18992,
                             max_seq_len=8192)
    assert mc.layer_runs() == ((0, 6, "sparse", "experts"),)
    shell, pshape, mb = _build_8b_shell(mc)
    rows, S, T = 2, 8192, 512
    shell.cfg.rollout.max_prompt_len = S - T
    shell.cfg.rollout.max_new_tokens = T
    shapes = {k: (S,) if k == "sequences" else () if k == "prompt_lens"
              else (T,) for k in mb}
    experience = {k: _sds((8,) + shapes[k], v.dtype, one_chip)
                  for k, v in mb.items()}
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         _abstract_state(shell, pshape))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
            donate_argnums=(0,)).lower(
                state, experience,
                _sds((8 // rows, rows), jnp.int32, one_chip)).compile()
    names = _kernel_names(compiled)
    assert names.count("dsa_select") == names.count("sparse_fwd") == 2
    assert names.count("sparse_bwd_dq") == names.count("sparse_bwd_dkv") == 1
    assert {"moe_gmm", "moe_gmm_dlhs", "moe_tgmm", "moe_combine"} <= set(
        names)
    assert not {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} & set(names)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(5.274e9, rel=5e-3)
    assert mem.peak_memory_in_bytes <= 12.0 * 2**30


@pytest.mark.parametrize("new_tokens, form", [(512, "kernel"),
                                              (256, "masked")])
def test_selected_step_reads_the_cache_in_place(new_tokens, form, one_chip,
                                                on_tpu):
    """The fixed-batch engine's whole program (prefill + the decode
    ``while_loop``) of ``ppo-keye-dsa-ep8-sync`` (two of its six layers)
    at 8 x 7680 + 512: the decode loop's body holds one ``sparse_step``
    kernel a layer, which takes k and v as they lie in the loop's cache
    (a bitcast: no copy or fusion of the cache's shape feeds it), and no
    gather of 2048 rows a sequence (``bf16[16384,4,128]``).  At 7680 +
    256 the cache is 15.5 of the kernel's blocks of 512 slots: XLA's
    einsum under the mask, no kernel, and no gather.  (7680 + 500, a
    cache of 8 x 1023 slots, compiles under neither: the prefill's
    ``sparse_fwd`` runs out of VMEM over such a cache.)"""
    import dataclasses
    import re

    from orion_tpu.config import ModelConfig, RolloutConfig
    from orion_tpu.models import Transformer, init_params
    from orion_tpu.models.transformer import cache_slots
    from orion_tpu.ops.pallas import sparse_step
    from orion_tpu.rollout.engine import RolloutEngine

    mc = dataclasses.replace(ModelConfig.keye_vl2_30b_a3b(), num_layers=2,
                             experts_held=16, vocab_size=18992,
                             max_seq_len=8192)
    assert sparse_step.step_form(cache_slots(7680 + new_tokens)) == form
    model = Transformer(mc)
    params = jax.tree.map(
        lambda x: _sds(x.shape, x.dtype, one_chip),
        jax.eval_shape(lambda: init_params(model, jax.random.key(0), mc)))
    eng = RolloutEngine(model, mc, RolloutConfig(
        max_prompt_len=7680, max_new_tokens=new_tokens), eos_token_id=0,
        pad_token_id=0)
    rng = jax.eval_shape(lambda: jax.random.key(0))
    with jax.default_matmul_precision("default"):
        compiled = eng._generate_jit.lower(
            params, _sds((8, 7680), jnp.int32, one_chip),
            _sds((8,), jnp.int32, one_chip),
            _sds(rng.shape, rng.dtype, one_chip),
            max_new_tokens=new_tokens).compile()
    text = compiled.as_text()
    assert "bf16[16384,4,128]" not in text
    # 8 rows of top-8 of 128 expect to need 0.40 of the 16 held stacks:
    # the expert layers' step reads the hit ones alone, whatever the cache
    _assert_step_experts(compiled, "kernel", 2, "bf16[8,16,1536]")
    if form == "masked":
        assert "sparse_step" not in _kernel_names(compiled)
        return
    assert _kernel_names(compiled).count("sparse_step") == 2
    decode = [body for body in _while_bodies(text).values()
              if "%sparse_step" in body]
    assert len(decode) == 1                     # the decode loop's body
    steps = re.findall(r"%sparse_step[.\d]* = [^\n]*", decode[0])
    assert len(steps) == 2
    for call in steps:
        operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
        k, v = [o.strip().lstrip("%") for o in operands.split(",")][2:4]
        for name in (k, v):
            made = re.search(r"%%%s = (\S+) (\w[\w\-]*)\(" % re.escape(name),
                             decode[0])
            assert made and made.group(2) == "bitcast", (name, made)
            assert made.group(1).startswith("bf16[8,32768,128]")


# -- the nemotron_h model at the published widths -----------------------------

def test_nemotron_h_update_compiles_for_v5e(one_chip, on_tpu):
    """The shared-backbone PPO update of ``ppo-nemotron-h-tp4-sync`` (the
    first period of Nemotron-3-Super at the published widths on a
    quarter of every mixer's heads, 8 of 512 experts, 16 384 rows of the
    vocabulary; remat, each stretch scanned) over 32 sequences of 1280
    in minibatches of 8.  The update has the flash kernels (the one
    attention block, 8 query heads against 1 key-value head) and the
    grouped products (relu^2 experts in the 1024-wide latent) in it; the
    Mamba-2 recurrence is XLA's.  773.6 M parameters: 6.19 GB of
    arguments (float32 master, two bf16 moments)."""
    import dataclasses

    from orion_tpu.config import ModelConfig
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc = dataclasses.replace(
        ModelConfig.nemotron_3_super_120b_a12b(), num_layers=11,
        head_share=(0, 4), experts_held=8, vocab_size=16384,
        max_seq_len=1280)
    assert mc.layer_runs() == ((0, 3, "mamba2", "experts"),
                               (3, 1, "mamba2", None),
                               (4, 1, "attention", "experts"),
                               (5, 1, "mamba2", "experts"))
    shell, pshape, mb = _build_8b_shell(mc)
    rows, S, T = 8, 1280, 1024
    shell.cfg.rollout.max_prompt_len = S - T
    shell.cfg.rollout.max_new_tokens = T
    shapes = {k: (S,) if k == "sequences" else () if k == "prompt_lens"
              else (T,) for k in mb}
    experience = {k: _sds((32,) + shapes[k], v.dtype, one_chip)
                  for k, v in mb.items()}
    state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         _abstract_state(shell, pshape))
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(
            lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
            donate_argnums=(0,)).lower(
                state, experience,
                _sds((32 // rows, rows), jnp.int32, one_chip)).compile()
    names = _kernel_names(compiled)
    assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "moe_gmm",
            "moe_gmm_dlhs", "moe_tgmm", "moe_combine"} <= set(names)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes == pytest.approx(6.19e9, rel=1e-2)
    # beside it the chip holds the bf16 reference (1.55 GB) and the batch
    assert mem.peak_memory_in_bytes + 1.6e9 <= V5E_BYTES_LIMIT


# -- the sdar_moe model (block diffusion) at the published widths -------------

def _sdar_cell():
    """(model configuration, B, P, T) of ``ppo-sdar-ep8-sync``."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    mc = dataclasses.replace(ModelConfig.sdar_30b_a3b(), num_layers=6,
                             experts_held=16, vocab_size=18992,
                             max_seq_len=1024)
    assert mc.layer_runs() == ((0, 6, "attention", "experts"),)
    return mc, 32, 256, 512


@pytest.mark.parametrize("program", ["generate", "experience", "update"])
def test_sdar_cell_programs_compile_for_v5e(program, one_chip, on_tpu):
    """The three programs of ``ppo-sdar-ep8-sync`` (6 of SDAR's 48 layers
    at the published widths, 16 of 128 experts, 18 992 rows of the
    vocabulary; remat, one scanned stack) at the timed shapes, 32
    prompts padded to 256 and 512 new tokens.  ``generate``: prefill and
    the block loop (4 denoising forwards a block: the first of 8 rows a
    sequence, the block before riding in front to be committed, the
    other three of 4: the prefill's flash kernel, no kernel in the
    steps' attention).  ``experience``: one trace forward of all 32 rows
    of 768 clean + 2176 noisy entries: ``flash_fwd`` twice a layer (the
    clean stream; the noisy queries' clean keys through the per-chunk
    entry).  ``update``: the same forward and its backward in
    minibatches of 4: the three flash kernels twice each and the grouped
    products.  Each fits beside what else the chip holds: 645.3 M
    parameters are 5.16 GB of float32 master and bf16 moments, the bf16
    reference 1.29 GB more."""
    from orion_tpu.config import RolloutConfig
    from orion_tpu.rollout.engine import RolloutEngine
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.trainers.ppo import PPOTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc, B, P, T = _sdar_cell()
    shell, pshape, mb = _build_8b_shell(mc)
    shell.cfg.rollout.max_prompt_len = P
    shell.cfg.rollout.max_new_tokens = T
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), pshape)
    ids = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    resident = 1.29e9              # the bf16 reference
    with jax.default_matmul_precision("default"):
        if program == "generate":
            eng = RolloutEngine(shell.model, mc, RolloutConfig(
                max_prompt_len=P, max_new_tokens=T), eos_token_id=None,
                pad_token_id=0)
            rng = jax.eval_shape(lambda: jax.random.key(0))
            compiled = eng._generate_jit.lower(
                params, ids(B, P), ids(B), _sds(rng.shape, rng.dtype,
                                                one_chip),
                max_new_tokens=T).compile()
            resident += 2 * 1.29e9          # and the moments
        elif program == "experience":
            compiled = jax.jit(
                lambda p, s, n, m, r: PPOTrainer._lp_values_fwd(
                    shell, p, s, n, m, max_new=T, with_entropy=False,
                    reveal_step=r)).lower(
                        params, ids(B, P + T), ids(B),
                        _sds((B, T), jnp.float32, one_chip),
                        ids(B, T)).compile()
            resident += 2 * 1.29e9
        else:
            rows = 4
            mb["reveal_step"] = jax.ShapeDtypeStruct((1, T), jnp.int32)
            shapes = {k: (P + T,) if k == "sequences"
                      else () if k == "prompt_lens" else (T,) for k in mb}
            experience = {k: _sds((B,) + shapes[k], v.dtype, one_chip)
                          for k, v in mb.items()}
            state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                                 _abstract_state(shell, pshape))
            compiled = jax.jit(
                lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
                donate_argnums=(0,)).lower(
                    state, experience,
                    _sds((B // rows, rows), jnp.int32, one_chip)).compile()
    names = _kernel_names(compiled)
    mem = compiled.memory_analysis()
    if program == "generate":
        assert "flash_fwd" in names                      # the prefill's
        assert not {"flash_bwd_dq", "paged_decode"} & set(names)
        assert mem.argument_size_in_bytes == pytest.approx(2.58e9, rel=1e-2)
        # forwards of 256 and 128 tokens select every held expert: the
        # einsum form, as before PR 54
        _assert_step_experts(compiled, "", 6, "bf16[128,16,1536]")
    elif program == "experience":
        assert names.count("flash_fwd") == 2
        assert {"moe_gmm", "moe_combine"} <= set(names)
    else:
        assert names.count("flash_fwd") == 4             # forward and remat's
        assert names.count("flash_bwd_dq") == 2
        assert names.count("flash_bwd_dkv") == 2
        assert {"moe_gmm", "moe_gmm_dlhs", "moe_tgmm",
                "moe_combine"} <= set(names)
        assert mem.argument_size_in_bytes == pytest.approx(5.16e9, rel=1e-2)
    assert mem.peak_memory_in_bytes + resident <= V5E_BYTES_LIMIT


# -- the lfm2_moe model (gated short convolutions) at the published widths ----

def _lfm2_cell():
    """(model configuration, B, P, T, minibatch) of ``ppo-lfm2-ep4-sync``."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    mc = dataclasses.replace(ModelConfig.lfm2_8b_a1b(), num_layers=8,
                             experts_held=8, vocab_size=16384,
                             max_seq_len=1280)
    assert mc.layer_runs() == (
        (0, 2, "conv", "dense"), (2, 1, "attention", "experts"),
        (3, 3, "conv", "experts"), (6, 1, "attention", "experts"),
        (7, 1, "conv", "experts"))
    return mc, 64, 256, 1024, 16


@pytest.mark.parametrize("program", ["generate", "experience", "update"])
def test_lfm2_cell_programs_compile_for_v5e(program, one_chip, on_tpu):
    """The three programs of ``ppo-lfm2-ep4-sync`` (the published layers
    0-7 of LFM2-8B-A1B, 8 of 32 experts, 16 384 rows of the vocabulary,
    the head tied; remat, each stretch scanned) at the timed shapes, 64
    prompts padded to 256 and 1024 new tokens.  ``generate``: prefill
    (``short_conv.chunk``, flash at heads of 64) and the decode loop
    (``short_conv.step`` on six layers, the kernel ``dense_step`` on
    two, four query heads a key head, over a cache laid ``bf16[64,
    1280, 512]``, the eight key heads of 64 side by side along the
    lanes and no lane padded: no ``conditional`` over prefixes and no
    copy of the cache or of a prefix of it, ISSUES 50 and 52; the six
    expert layers' step the kernel ``experts_step``, 64 rows against
    every held stack, ISSUE 57; the
    program's temporaries are 3.089 GB and its peak 5.967 GB where the
    per-head cache ``bf16[64, 1280, 8, 64]``, each row of 64 in 128
    lanes, made them 3.992 and 6.307: 340 MB less at the peak, four
    arrays of 84 MB in place of 168).  ``experience``: one forward of all 64
    rows.  ``update``: the forward, remat's and the backward in
    minibatches of 16.  No kernel of the convolution's own: XLA fuses
    the taps between the two products.  Each fits beside what else the
    chip holds: 772.2 M parameters are 6.18 GB of float32 master and
    bf16 moments, the bf16 reference 1.54 GB more."""
    import re

    from orion_tpu.config import RolloutConfig
    from orion_tpu.rollout.engine import RolloutEngine
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.trainers.ppo import PPOTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc, B, P, T, rows = _lfm2_cell()
    shell, pshape, mb = _build_8b_shell(mc)
    shell.cfg.rollout.max_prompt_len = P
    shell.cfg.rollout.max_new_tokens = T
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), pshape)
    ids = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    resident = 1.54e9              # the bf16 reference
    with jax.default_matmul_precision("default"):
        if program == "generate":
            eng = RolloutEngine(shell.model, mc, RolloutConfig(
                max_prompt_len=P, max_new_tokens=T), eos_token_id=None,
                pad_token_id=0)
            rng = jax.eval_shape(lambda: jax.random.key(0))
            lowered = eng._generate_jit.lower(
                params, ids(B, P), ids(B), _sds(rng.shape, rng.dtype,
                                                one_chip),
                max_new_tokens=T)
            resident += 2 * 1.54e9          # and the moments
        elif program == "experience":
            lowered = jax.jit(
                lambda p, s, n, m: PPOTrainer._lp_values_fwd(
                    shell, p, s, n, m, max_new=T, with_entropy=False)).lower(
                        params, ids(B, P + T), ids(B),
                        _sds((B, T), jnp.float32, one_chip))
            resident += 2 * 1.54e9
        else:
            shapes = {k: (P + T,) if k == "sequences"
                      else () if k == "prompt_lens" else (T,) for k in mb}
            experience = {k: _sds((B,) + shapes[k], v.dtype, one_chip)
                          for k, v in mb.items()}
            state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                                 _abstract_state(shell, pshape))
            lowered = jax.jit(
                lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
                donate_argnums=(0,)).lower(
                    state, experience,
                    _sds((B // rows, rows), jnp.int32, one_chip))
        scopes = lowered.as_text(debug_info=True)
        compiled = lowered.compile()
    names = _kernel_names(compiled)
    mem = compiled.memory_analysis()
    assert "short_conv.chunk" in scopes
    if program == "generate":
        assert "short_conv.step" in scopes
        assert "flash_fwd" in names                      # the prefill's
        assert not {"flash_bwd_dq", "paged_decode"} & set(names)
        assert mem.argument_size_in_bytes == pytest.approx(3.09e9, rel=1e-2)
        text = compiled.as_text()
        assert names.count("dense_step") == 2
        (decode,) = [name for name, body in _while_bodies(text).items()
                     if "%dense_step" in body]
        comps = _computations(text)
        # the step does not switch over prefixes of the cache, and
        # nothing the loop runs copies or re-lays the cache or a prefix
        # of it (PR 49's 32 ``copy bf16[64,<prefix>,8,64]{1,3,2,0}``)
        cache = r"bf16\[64,\d+,(?:8,64|512)\]"
        for name in _called(comps, decode):
            assert " conditional(" not in comps[name]
            assert not re.search(r"= %s\S* copy(-start)?\(" % cache,
                                 comps[name]), name
        # the prefill's attention reads the cache by head: the re-laid
        # copies of the two layers' K and V, once a rollout, outside the
        # loop
        relaid = [ln for name in set(comps) - _called(comps, decode)
                  for ln in comps[name].splitlines()
                  if re.search(r"= %s\S* copy\(" % cache, ln)]
        assert len(relaid) >= 4 and all("/prefill/" in ln for ln in relaid)
        # the kernel takes k and v as the loop carries and writes them:
        # [64, 1280, 512] in tiles of 16 x 128 bf16, nothing padded
        steps = re.findall(r"%dense_step[.\d]* = [^\n]*", comps[decode])
        assert len(steps) == 2
        laid = "bf16[64,1280,512]{2,1,0:T(8,128)(2,1)}"
        assert comps[decode].count(                          # the carry
            "= %s get-tuple-element(" % laid) == 4
        for call in steps:
            operands = re.search(r"custom-call\(([^)]*)\)", call).group(1)
            for name in [o.strip().lstrip("%")
                         for o in operands.split(",")][3:5]:
                made = re.search(
                    r"%%%s = (\S+) (\w[\w\-]*)\(" % re.escape(name),
                    comps[decode])
                # (the row's scatter, in place)
                assert made and made.group(2) == "fusion", (name, made)
                assert made.group(1) == laid
        assert "bf16[64,10240,64]" not in text
        # 64 rows of top-4 of 32 select every held expert (0.9998) and
        # are few enough for the kernel all the same (PR 57): one
        # ``experts_step`` a layer, no einsum form's gate / up product
        _assert_step_experts(compiled, "kernel", 6, "bf16[64,8,3584]")
        assert mem.temp_size_in_bytes == pytest.approx(3.089e9, rel=1e-2)
        assert mem.peak_memory_in_bytes == pytest.approx(5.967e9, rel=1e-2)
    elif program == "experience":
        assert "short_conv.step" not in scopes
        assert names.count("flash_fwd") == 2     # one a stretch of attention
        assert {"moe_gmm", "moe_combine"} <= set(names)
    else:
        assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "moe_gmm",
                "moe_gmm_dlhs", "moe_tgmm", "moe_combine"} <= set(names)
        assert mem.argument_size_in_bytes == pytest.approx(6.18e9, rel=1e-2)
    assert mem.peak_memory_in_bytes + resident <= V5E_BYTES_LIMIT


# -- the mellum model (sliding-window layers beside full ones) ---------------

def _mellum_cell():
    """(model configuration, B, P, T, minibatch) of
    ``ppo-mellum2-ep8-sync``."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    mc = dataclasses.replace(ModelConfig.mellum2_12b_a2_5b(), num_layers=8,
                             experts_held=8, vocab_size=12288,
                             max_seq_len=8192)
    assert mc.layer_runs() == (
        (0, 3, "window", "experts"), (3, 1, "attention", "experts"),
        (4, 3, "window", "experts"), (7, 1, "attention", "experts"))
    return mc, 8, 7168, 1024, 2


@pytest.mark.parametrize("program", ["generate", "update"])
def test_mellum_cell_programs_compile_for_v5e(program, one_chip, on_tpu):
    """The two largest programs of ``ppo-mellum2-ep8-sync`` (the published
    layers 0-7 of Mellum2-12B-A2.5B, S S S F S S S F, 8 of 64 experts,
    12 288 rows of the vocabulary; remat, each stretch scanned) at the
    timed shapes, 8 prompts padded to 7168 and 1024 new tokens.
    ``generate``: prefill (``flash_fwd_window`` on six layers over their
    own keys, ``flash_fwd`` on two over the cache) and the decode loop,
    the kernel ``dense_step`` on all eight layers, eight query heads a
    key head of 128 (whole-lane heads: ``Hkv * D`` = 512 lanes), six over
    rings laid ``bf16[8, 1024, 512]`` and two over caches ``bf16[8, 8192,
    512]``: no ``conditional`` over prefixes and no copy of a cache or
    ring inside the loop.  (The experience forward, one forward of all 8
    rows, is the update's forward at four times the rows: peak 7.06 GB by
    the same compile, PR 53; not compiled here, for the suite's clock.)
    ``update``: the forward, remat's and the backward in minibatches of
    2, the three windowed kernels beside the three full ones.  Each fits
    beside what else the chip holds: 624 M parameters are 4.99 GB of
    float32 master and bf16 moments, the bf16 reference 1.25 GB more."""
    import re

    from orion_tpu.config import RolloutConfig
    from orion_tpu.rollout.engine import RolloutEngine
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc, B, P, T, rows = _mellum_cell()
    shell, pshape, mb = _build_8b_shell(mc)
    shell.cfg.rollout.max_prompt_len = P
    shell.cfg.rollout.max_new_tokens = T
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), pshape)
    ids = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    resident = 1.25e9              # the bf16 reference
    with jax.default_matmul_precision("default"):
        if program == "generate":
            eng = RolloutEngine(shell.model, mc, RolloutConfig(
                max_prompt_len=P, max_new_tokens=T), eos_token_id=None,
                pad_token_id=0)
            rng = jax.eval_shape(lambda: jax.random.key(0))
            lowered = eng._generate_jit.lower(
                params, ids(B, P), ids(B), _sds(rng.shape, rng.dtype,
                                                one_chip),
                max_new_tokens=T)
            resident += 2 * 1.25e9          # and the moments
        else:
            shapes = {k: (P + T,) if k == "sequences"
                      else () if k == "prompt_lens" else (T,) for k in mb}
            experience = {k: _sds((B,) + shapes[k], v.dtype, one_chip)
                          for k, v in mb.items()}
            state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                                 _abstract_state(shell, pshape))
            lowered = jax.jit(
                lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
                donate_argnums=(0,)).lower(
                    state, experience,
                    _sds((B // rows, rows), jnp.int32, one_chip))
        scopes = lowered.as_text(debug_info=True)
        compiled = lowered.compile()
    names = _kernel_names(compiled)
    mem = compiled.memory_analysis()
    print(program, {k: getattr(mem, k) for k in _MEMORY},
          sorted(set(names)))
    assert "attn.window" in scopes and "attn.full" in scopes
    if program == "generate":
        # the prefill: two stretches of each kind, unrolled in the twin
        assert names.count("flash_fwd_window") == 6
        assert names.count("flash_fwd") == 2
        assert not {"flash_bwd_dq", "flash_dq_window", "paged_decode"} \
            & set(names)
        text = compiled.as_text()
        assert names.count("dense_step") == 8
        (decode,) = [name for name, body in _while_bodies(text).items()
                     if "%dense_step" in body]
        comps = _computations(text)
        cache = r"bf16\[8,(?:1024|8192),(?:4,128|512)\]"
        for name in _called(comps, decode):
            assert " conditional(" not in comps[name]
            assert not re.search(r"= %s\S* copy(-start)?\(" % cache,
                                 comps[name]), name
        # the loop carries six rings and two caches, K and V each, laid
        # for the kernel: nothing padded
        ring = "bf16[8,1024,512]{2,1,0:T(8,128)(2,1)}"
        full = "bf16[8,8192,512]{2,1,0:T(8,128)(2,1)}"
        assert comps[decode].count("= %s get-tuple-element(" % ring) == 12
        assert comps[decode].count("= %s get-tuple-element(" % full) == 4
        # 8 rows of top-8 of 64 expect to need 0.66 of the 8 held stacks
        _assert_step_experts(compiled, "kernel", 8, "bf16[8,8,1792]")
        assert comps[decode].count("%experts_step") >= 8
        assert mem.argument_size_in_bytes == pytest.approx(2.50e9, rel=1e-2)
    else:
        assert {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                "flash_fwd_window", "flash_dq_window", "flash_dkv_window",
                "moe_gmm", "moe_gmm_dlhs", "moe_tgmm", "moe_combine"} \
            <= set(names)
        assert mem.argument_size_in_bytes == pytest.approx(4.99e9, rel=2e-2)
    assert mem.peak_memory_in_bytes + resident <= V5E_BYTES_LIMIT


# -- the ouro model (one stack run several times over) -----------------------

def _ouro_cell():
    """(model configuration, B, P, T, minibatch) of ``ppo-ouro-d8-sync``."""
    import dataclasses

    from orion_tpu.config import ModelConfig

    mc = dataclasses.replace(ModelConfig.ouro_2_6b(), num_layers=8,
                             max_seq_len=768)
    assert mc.layer_runs() == ((0, 8, "attention", "dense"),)
    assert mc.layer_visits() == 32
    return mc, 16, 256, 512, 4


@pytest.mark.parametrize("program", ["generate", "experience", "update"])
def test_ouro_cell_programs_compile_for_v5e(program, one_chip, on_tpu):
    """The three programs of ``ppo-ouro-d8-sync`` (the published layers
    0-7 of Ouro-2.6B run four times over with one set of weights; remat,
    the stack scanned under a scan over passes) at the timed shapes, 16
    prompts padded to 256 and 512 new tokens.  ``generate``: prefill and
    the decode loop through the twin, its 8 layers unrolled and its
    passes a scan that carries the cache: ONE pass's blocks in the text
    (``flash_fwd`` once a layer in the prefill's pass body), the decode
    step's loop over passes carrying K and V of the 8 layers as they
    lie, ``bf16[4, 16, 768, 16, 128]`` (one query head a key head:
    ``dense_step.step_form`` says ``""``, so ``prefix_step``'s
    ``conditional`` over 6 prefixes of 128 slots a layer, each branch
    slicing its pass's prefix out of the leaf inside its fusions), the
    new rows scattered into the leaves in place: no copy of a leaf nor
    of a pass's entry anywhere in the step.  ``experience``: the shared-trunk
    forward of all 16 rows, which sows the exit masses.  ``update``: the
    forward, remat's and the backward in minibatches of 4 with ONE block
    body whatever the passes (three flash kernels, each once in the
    text).  Each fits beside what else the chip holds: 612 M parameters
    are 4.90 GB of float32 master and bf16 moments, the bf16 reference
    1.22 GB more."""
    import re

    from orion_tpu.config import RolloutConfig
    from orion_tpu.models.transformer import remat_tag_bytes
    from orion_tpu.rollout.engine import RolloutEngine
    from orion_tpu.trainers.base import BaseTrainer
    from orion_tpu.utils.compile_check import (_abstract_state,
                                               _build_8b_shell)

    mc, B, P, T, rows = _ouro_cell()
    shell, pshape, mb = _build_8b_shell(mc)
    shell.cfg.rollout.max_prompt_len = P
    shell.cfg.rollout.max_new_tokens = T
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), pshape)
    ids = lambda *shape: _sds(shape, jnp.int32, one_chip)  # noqa: E731
    resident = 1.22e9              # the bf16 reference
    with jax.default_matmul_precision("default"):
        if program == "generate":
            eng = RolloutEngine(shell.model, mc, RolloutConfig(
                max_prompt_len=P, max_new_tokens=T), eos_token_id=None,
                pad_token_id=0)
            rng = jax.eval_shape(lambda: jax.random.key(0))
            lowered = eng._generate_jit.lower(
                params, ids(B, P), ids(B), _sds(rng.shape, rng.dtype,
                                                one_chip),
                max_new_tokens=T)
            resident += 2 * 1.22e9          # and the moments
        elif program == "experience":
            lowered = jax.jit(
                lambda p, s, n, m: shell._lp_values_fwd(
                    p, s, n, m, max_new=T, with_entropy=False)).lower(
                params, ids(B, P + T), ids(B),
                _sds((B, T), jnp.float32, one_chip))
            resident += 2 * 1.22e9
        else:
            shapes = {k: (P + T,) if k == "sequences"
                      else () if k == "prompt_lens" else (T,) for k in mb}
            experience = {k: _sds((B,) + shapes[k], v.dtype, one_chip)
                          for k, v in mb.items()}
            state = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                                 _abstract_state(shell, pshape))
            def update(keep):
                shell._remat_keep = keep
                return jax.jit(
                    lambda s, e, i: BaseTrainer._epochs_fn(shell, s, e, i),
                    donate_argnums=(0,)).lower(
                        state, experience,
                        _sds((B // rows, rows), jnp.int32, one_chip))

            lowered = update(())
            # what the remat ladder reckons a kept tag holds IS what the
            # compiled update holds more: four passes' stacks in the scan
            # over passes' own, and the newest pass's once more in the
            # scan over layers' (remat_tag_bytes: passes + 1)
            tags = dict(remat_tag_bytes(mc, rows, P + T, lane=128))
            kept = update(("mlp_pre",)).compile().memory_analysis()
        compiled = lowered.compile()
    names = _kernel_names(compiled)
    mem = compiled.memory_analysis()
    print(program, {k: getattr(mem, k) for k in _MEMORY},
          sorted(set(names)))
    if program == "update":
        assert tags["mlp_pre"] == 5 * 8 * 2 * rows * (P + T) * 5632 * 2
        # by the ladder's own measure (trainers/base.py::_probe_remat_keep)
        def need(m):
            return (m.peak_memory_in_bytes - m.argument_size_in_bytes
                    + m.generated_code_size_in_bytes)

        assert need(kept) - need(mem) == pytest.approx(tags["mlp_pre"],
                                                       rel=0.03)
    if program == "generate":
        # the prefill: one pass's layers in the text
        assert names.count("flash_fwd") == 8
        assert not {"flash_bwd_dq", "dense_step", "paged_decode"} \
            & set(names)
        text = compiled.as_text()
        comps = _computations(text)
        bodies = _while_bodies(text)
        leaf = "bf16[4,16,768,16,128]"
        # the decode step's loop over passes: a conditional a layer
        (passes,) = [name for name, body in bodies.items()
                     if body.count(" conditional(") == 8]
        found = re.findall(r" conditional\(.*branch_computations=\{([^}]*)\}",
                           comps[passes])
        assert all(len(f.split(",")) == 6 for f in found)
        # K and V of the 8 layers ride it as they lie, written in place
        assert len(re.findall(r"= %s\S* fusion\(" % re.escape(leaf),
                              comps[passes])) == 16
        (step,) = [name for name, body in bodies.items()
                   if "body=%" + passes in body]
        entry = "bf16[16,768,16,128]"
        for name in _called(comps, step):
            for whole in (leaf, entry):
                assert not re.search(r"= %s\S* (copy(-start)?|dynamic-slice)"
                                     r"\(" % re.escape(whole),
                                     comps[name]), (name, whole)
        assert mem.argument_size_in_bytes == pytest.approx(2.45e9, rel=1e-2)
    elif program == "experience":
        assert names.count("flash_fwd") == 1
        assert mem.argument_size_in_bytes == pytest.approx(2.45e9, rel=1e-2)
    else:
        # one block body whatever the passes
        assert [names.count(k) for k in
                ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")] == [2, 1, 1]
        assert mem.argument_size_in_bytes == pytest.approx(4.90e9, rel=2e-2)
    assert mem.peak_memory_in_bytes + resident <= V5E_BYTES_LIMIT
