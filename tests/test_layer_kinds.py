"""The table of layer kinds (``models/transformer.py::MIXERS``) and the
one ``Block``: shapes only (``jax.eval_shape``), no arithmetic.

- the parameter tree of every tiny model equals the tree recorded at
  the commit before the three block classes became one
  (``tests/fixtures/param_trees.json``): checkpoints, the HF key maps
  and the reference checks read parameter paths;
- the cache entry a mixer STATES (``cache_entry``) is the one its
  module RETURNS, from a prefill and from a one-token step;
- the seam stays where it is: outside the model layer nothing branches
  on an arch or on a kind's name.
"""

import json
import pathlib
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from orion_tpu.config import ModelConfig
from orion_tpu.models.transformer import MIXERS, Transformer, mixer_spec

REPO = pathlib.Path(__file__).resolve().parent.parent
ARCHS = ("llama", "neox", "deepseek_v3", "kimi_linear", "olmo_hybrid",
         "keye_dsa", "nemotron_h", "sdar_moe", "lfm2_moe", "mellum", "ouro")
#: a tiny arch whose layers have the mixer
MIXER_ARCH = {"attention": "llama", "sparse": "keye_dsa",
              "latent": "deepseek_v3", "kda": "kimi_linear",
              "gdn": "olmo_hybrid", "mamba2": "nemotron_h",
              "conv": "lfm2_moe", "window": "mellum"}


def _shapes(tree) -> dict:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in path): [list(x.shape), str(x.dtype)]
            for path, x in flat}


@pytest.mark.parametrize("layout", ["unrolled", "scanned"])
@pytest.mark.parametrize("arch", ARCHS)
def test_the_parameter_tree_is_the_recorded_one(arch, layout):
    recorded = json.loads(
        (REPO / "tests/fixtures/param_trees.json").read_text())
    cfg = ModelConfig.tiny(arch, scan_layers=layout == "scanned")
    ids = jax.ShapeDtypeStruct((1, 2), jnp.int32)
    params = jax.eval_shape(Transformer(cfg).init, jax.random.key(0),
                            ids, ids)["params"]
    assert _shapes(nn.meta.unbox(params)) == recorded[f"{arch}/{layout}"]


@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_a_mixer_returns_the_cache_entry_it_states(mixer):
    assert set(MIXER_ARCH) == set(MIXERS)
    cfg = ModelConfig.tiny(MIXER_ARCH[mixer])
    assert mixer in {m for m, _ in cfg.layer_kinds()}
    kind, kw = mixer_spec(cfg, mixer)
    module = kind(cfg, **kw)
    B, P, slots = 2, 16, 40          # tiny keye_dsa selects 8 keys of 16
    more = (None,) if kind.takes_token_mask else ()

    def run():
        entry = kind.cache_entry(cfg, B, slots, jnp.dtype(cfg.dtype))
        x = jnp.zeros((B, P, cfg.hidden_size), jnp.dtype(cfg.dtype))
        pos = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32), (B, P))
        params = module.init(jax.random.key(0), x, pos)
        _, prefill = module.apply(params, x, pos, entry, *more)
        _, step = module.apply(params, x[:, :1], pos[:, -1:] + 1, prefill,
                               *more)
        return entry, prefill, step

    entry, prefill, step = jax.eval_shape(run)
    assert _shapes(entry) == _shapes(prefill) == _shapes(step)
    assert set(kind.index_leaves) <= set(entry)
    assert kind.cache_kind in ("cache", "state")
    # a state has no axis of slots, a cache has one in every leaf (a
    # windowed layer's ring its own fewer)
    held = getattr(kind, "ring_slots", lambda cfg, n: n)(cfg, slots)
    assert all((held in x.shape) == (kind.cache_kind == "cache")
               for x in jax.tree.leaves(entry))


def _hits(pattern: str, *paths: str) -> list:
    found = []
    for path in paths:
        files = [REPO / path] if path.endswith(".py") \
            else sorted((REPO / path).rglob("*.py"))
        for file in files:
            for n, line in enumerate(file.read_text().splitlines(), 1):
                if re.search(pattern, line):
                    found.append(f"{file.relative_to(REPO)}:{n}")
    return found


def test_the_seam_stays_in_the_model_layer():
    """ISSUE 45's acceptance greps: an arch picks something only in the
    config, the two HF key maps and ``mixer_spec``; the trainer names no
    kind's facts; the engines tell no kind by its cache's keys."""
    allowed = ("orion_tpu/config.py", "orion_tpu/models/hf_loader.py",
               "orion_tpu/models/hf_export.py")
    arch = [h for h in _hits(r"arch\s*(==|!=|in)\s", "orion_tpu")
            if not h.startswith(allowed)]
    source = (REPO / "orion_tpu/models/transformer.py").read_text()
    before, spec = source.split("def mixer_spec(")
    start = before.count("\n") + 1
    end = start + spec.split("\n\n\n")[0].count("\n")
    assert arch and all(
        h.startswith("orion_tpu/models/transformer.py:")
        and start <= int(h.rsplit(":", 1)[1]) <= end for h in arch), arch
    assert _hits(r"sa_topk|sa_key|sa_step|kda_|delta_head_dims|heads_held"
                 r"|hasattr\(eng", "orion_tpu/trainers/base.py") == []
    assert _hits(r'" in layer', "orion_tpu/rollout") == []
    assert _hits(r"latent_attention|\.recurrent\b|moe_activation",
                 "orion_tpu/rollout/engine.py",
                 "orion_tpu/rollout/continuous.py") == []
    assert len(re.findall(r"^class \w*Block\(", source, re.M)) == 1
