"""``model.remat`` recomputes what does not fit: the blocks tag what is
dear to recompute (models/transformer.py, ``REMAT_TAGS``) and the
update's checkpoints keep the tags that the device's free bytes hold.

A kept tensor is the value the recomputation would produce, so loss and
gradients must not move whatever is kept; the ladder is a pure function;
the bytes it reckons from shapes are the bytes jax saves; and the choice
is on the ``update`` span.  The CPU reports no memory, so a trainer here
keeps nothing unless a test gives it a device that does.
"""

import contextlib
import dataclasses
import functools
import io
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu import obs
from orion_tpu.config import ModelConfig, PPOConfig
from orion_tpu.models import Transformer, init_params
from orion_tpu.models.transformer import (REMAT_TAGS, remat_keep,
                                          remat_tag_bytes)
from orion_tpu.ops import moe

from test_trainers import lucky_token_reward, prompt_stream, _mk

B, L = 2, 16

MODELS = {
    "neox": lambda: ModelConfig.tiny("neox", dtype="float32"),
    "deepseek_v3": lambda: ModelConfig.tiny("deepseek_v3", dtype="float32"),
    "llama": lambda: ModelConfig.tiny("llama"),
    "neox_parallel": lambda: ModelConfig.tiny(
        "neox", use_parallel_residual=True),
}
# the tags each of the two cells' blocks have, in ladder order
TAGS = {"neox": REMAT_TAGS[1:], "deepseek_v3": REMAT_TAGS}
PREFIXES = [(m, k) for m in TAGS for k in range(1, len(TAGS[m]) + 1)]


@pytest.fixture(autouse=True)
def grouped_experts(monkeypatch):
    """The expert layer takes its grouped form (the one the sort and
    its tags are in) at the tiny size too."""
    monkeypatch.setattr(moe, "DENSE_MAX_TOKENS", 0)


@functools.lru_cache(maxsize=None)
def _setup(name: str, scan: bool):
    """(loss(params, keep), params, tags with bytes): the flash kernel
    (interpreted), since its residuals carry the attention tags."""
    cfg = dataclasses.replace(MODELS[name](), remat=True, scan_layers=scan,
                              attention_impl="flash")
    model = Transformer(cfg)
    params = init_params(model, jax.random.key(0), cfg)
    ids = jax.random.randint(jax.random.key(1), (B, L), 0, cfg.vocab_size)
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    def loss(p, keep):
        logits, _ = model.apply({"params": p}, ids, pos, remat_keep=keep)
        lp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(lp, ids[..., None], axis=-1))

    return loss, params, remat_tag_bytes(cfg, B, L)


@functools.lru_cache(maxsize=None)
def _nothing_kept(name: str, scan: bool):
    loss, params, _ = _setup(name, scan)
    return jax.value_and_grad(lambda p: loss(p, ()))(params)


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("name,k", PREFIXES,
                         ids=[f"{m}-{TAGS[m][k - 1]}" for m, k in PREFIXES])
def test_each_prefix_kept_equals_nothing_kept(name, k, scan):
    loss, params, tags = _setup(name, scan)
    assert tuple(t for t, _ in tags) == TAGS[name]
    keep = TAGS[name][:k]
    l0, g0 = _nothing_kept(name, scan)
    l1, g1 = jax.value_and_grad(lambda p: loss(p, keep))(params)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l0),
                               rtol=1e-6, atol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


# -- the ladder ------------------------------------------------------------

RUNGS = (("a", 10), ("b", 20), ("c", 5))


@pytest.mark.parametrize("budget,kept", [
    (None, ()), (0, ()), (9, ()), (10, ("a",)), (29, ("a",)),
    (30, ("a", "b")), (34, ("a", "b")), (35, ("a", "b", "c")),
    (1 << 40, ("a", "b", "c")),
    # a rung that does not fit ends the ladder: "c" alone would fit
    (15, ("a",)),
])
def test_ladder_keeps_the_names_before_the_budget(budget, kept):
    assert remat_keep(RUNGS, budget) == kept


def test_no_budget_is_the_program_without_a_policy():
    """Nothing kept, whether by no budget, by none left or by a caller
    that says nothing (the pipeline's own blocks, compile_check), lowers
    to one text; something kept lowers to another."""
    loss, params, tags = _setup("neox", True)

    def text(keep):
        return jax.jit(jax.grad(lambda p: loss(p, keep))).lower(
            params).as_text()

    assert remat_keep(tags, None) == remat_keep(tags, 0) == ()
    plain = text(())
    assert "optimization_barrier" in plain     # the checkpoint is there
    assert text(remat_keep(tags, None)) == plain
    assert text(remat_keep(tags, tags[0][1])) != plain
    # and a forward alone takes no checkpoint: the names are nothing
    fwd = [jax.jit(lambda p, k=k: loss(p, k)).lower(params).as_text()
           for k in ((), TAGS["neox"])]
    assert fwd[0] == fwd[1]


def _saved_bytes(fn, *args) -> int:
    """Bytes of what ``fn``'s backward keeps beyond its arguments, from
    ``print_saved_residuals`` (jax 0.9.0 has no public list)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        jax.ad_checkpoint.print_saved_residuals(fn, *args)
    total = 0
    for line in buf.getvalue().splitlines():
        if " from the argument " in line or "from a constant" in line:
            continue
        dtype, shape = re.match(r"(\w+)\[([\d,]*)\]", line).groups()
        total += int(np.prod([int(d) for d in shape.split(",") if d])) \
            * jnp.dtype({"f32": "float32", "i32": "int32",
                         "bf16": "bfloat16"}[dtype]).itemsize
    return total


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scanned"])
@pytest.mark.parametrize("name", sorted(MODELS))
def test_bytes_reckoned_from_shapes_are_the_bytes_saved(name, scan):
    """Each tag adds to the saved residuals exactly what
    ``remat_tag_bytes`` says (``lane=1``: elements as they are)."""
    loss, params, tags = _setup(name, scan)
    names = [t for t, _ in tags]
    saved = [_saved_bytes(lambda p, k=k: loss(p, tuple(names[:k])), params)
             for k in range(len(names) + 1)]
    assert list(np.diff(saved)) == [b for _, b in tags]


def test_bytes_on_the_device_pad_the_last_dimension():
    cfg = MODELS["deepseek_v3"]()
    held = dict(remat_tag_bytes(cfg, B, L, lane=128))
    counted = dict(remat_tag_bytes(cfg, B, L))
    assert held.keys() == counted.keys()
    assert all(held[t] > counted[t] for t in held)     # every width < 128
    # the two cells' widths are multiples of 128 but the latent keys (192)
    big = ModelConfig.pythia_1b()
    assert remat_tag_bytes(big, 16, 384, 128) == remat_tag_bytes(big, 16, 384)
    # 16 layers of 16 x 384 tokens, in MiB: up projection, the kernel's
    # output and its float32 row statistics, q k v
    assert [b >> 20 for _, b in remat_tag_bytes(big, 16, 384)] == [
        1536, 384 + 3, 1152]


# -- the trainer's choice, and the counter ---------------------------------

def _ppo(**model_kw):
    from orion_tpu.models.heads import (ActorCriticModel,
                                        wrap_actor_critic_params)
    from orion_tpu.trainers import PPOTrainer

    cfg = _mk(PPOConfig, share_backbone=True, num_epochs=1,
              minibatch_size=4)
    cfg.model = ModelConfig.tiny(
        "neox", dtype="float32", vocab_size=32, hidden_size=32,
        intermediate_size=64, num_heads=2, num_kv_heads=2,
        scan_layers=True, remat=True, attention_impl="flash", **model_kw)
    model = ActorCriticModel(cfg.model)
    params = wrap_actor_critic_params(
        init_params(Transformer(cfg.model), jax.random.key(0), cfg.model),
        cfg.model)
    return PPOTrainer(cfg, model, params, reward_fn=lucky_token_reward,
                      eos_token_id=None)


def _update_spans(trainer, iterations=2):
    """(metrics rows, attributes of the ``update`` spans); the times
    the update was traced are left on ``trainer.update_traces``."""
    traces, loss_fn = [], trainer.loss_fn

    def counted(params, mb):
        traces.append(trainer._remat_keep)
        return loss_fn(params, mb)

    trainer.loss_fn, trainer.update_traces = counted, traces
    tracer = obs.Tracer(ring_size=256, enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        hist = trainer.train(prompt_stream(4, 4), num_iterations=iterations)
    finally:
        obs.set_tracer(prev)
        trainer.close()
    return hist, [e["attrs"] for e in tracer.events()
                  if e["name"] == "update"]


def test_a_device_that_reports_nothing_keeps_nothing():
    """The CPU: no budget, today's program, and the span says so."""
    trainer = _ppo()
    hist, spans = _update_spans(trainer)
    assert trainer._remat_keep == () and trainer.update_traces == [()]
    want = {"remat_kept": "", "remat_kept_bytes": 0,
            "remat_budget_bytes": 0, "kda_chunk": ""}    # no KDA layer
    assert len(spans) == 2
    for attrs, row in zip(spans, hist):
        assert {k: attrs[k] for k in want} == want
        assert {k: row[k] for k in want} == want


def test_the_choice_follows_the_free_bytes_and_is_on_the_span(monkeypatch):
    """A device with free bytes: the update is compiled with nothing
    kept for its own need, the ladder takes what fits beside it, and
    there is one update program from then on.  Where nothing fits, the
    program compiled for the reading is the update."""
    from orion_tpu.trainers import base

    free = 1 << 40
    monkeypatch.setattr(base, "_device_free_bytes", lambda tree: free)
    trainer = _ppo()
    hist, spans = _update_spans(trainer)
    # minibatches of 4 sequences: prompts of 4 + 8 new tokens
    tags = remat_tag_bytes(trainer.cfg.model, 4, 4 + 8, lane=128)
    assert trainer._remat_keep == tuple(t for t, _ in tags)
    # traced for the reading with nothing kept, then once with the names
    assert trainer.update_traces == [(), trainer._remat_keep]
    budget = spans[0]["remat_budget_bytes"]
    need = free - base._REMAT_MARGIN_BYTES - budget
    assert 0 < need < 1 << 30           # a tiny update's temporaries
    for attrs, row in zip(spans, hist):
        assert attrs["remat_kept"] == row["remat_kept"] \
            == "attn_resid+mlp_pre+attn_out+attn_qkv"
        assert attrs["remat_kept_bytes"] == sum(b for _, b in tags)
        assert attrs["remat_budget_bytes"] == budget
    assert all(np.isfinite(r["loss"]) for r in hist)

    # free bytes for the update's own need, the margin and two rungs
    free = need + base._REMAT_MARGIN_BYTES + tags[0][1] + tags[1][1] + 1
    trainer = _ppo()
    hist2, spans = _update_spans(trainer, iterations=1)
    assert trainer.update_traces == [(), ("attn_resid", "mlp_pre")]
    assert spans[0]["remat_kept_bytes"] == tags[0][1] + tags[1][1]
    # what is kept changes no number (same seed, same batch)
    np.testing.assert_allclose(hist2[0]["loss"], hist[0]["loss"], rtol=1e-5)

    free = need            # the update's own need and not a byte more
    trainer = _ppo()
    _, spans = _update_spans(trainer)
    assert trainer.update_traces == [()] and spans[1]["remat_kept"] == ""
    assert spans[1]["remat_budget_bytes"] == 0


def test_the_kept_names_survive_the_xplane(monkeypatch, tmp_path):
    """The profiler writes a span's attributes as ``name#k=v,k=v#`` and
    cuts a value at its first comma: the kept tags are joined with
    ``+``, so the ``update`` span read back from a session's xplane by
    the benchmark's ``host_spans`` holds every name (ISSUE 36)."""
    import glob
    import importlib.util
    import os

    from orion_tpu.trainers import base

    monkeypatch.setattr(base, "_device_free_bytes", lambda tree: 1 << 40)
    trainer = _ppo()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        hist = trainer.train(prompt_stream(4, 4), num_iterations=2)
    finally:
        jax.profiler.stop_trace()
        trainer.close()
    kept = trainer._remat_keep
    assert len(kept) > 1 and hist[0]["remat_kept"] == "+".join(kept)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spec = importlib.util.spec_from_file_location(
        "host_spans_for_remat", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "host_spans.py"))
    hs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(hs)
    updates = [sp for _, spans in hs.load(path).threads for sp in spans
               if sp.name == "update"]
    assert len(updates) == 2
    for sp in updates:
        assert tuple(sp.stats["remat_kept"].split("+")) == kept
        assert int(sp.stats["remat_kept_bytes"]) == hist[0]["remat_kept_bytes"]
