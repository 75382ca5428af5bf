"""Data layer + CLI launcher tests (SURVEY.md §2 #15-16)."""

import json

import numpy as np
import pytest

from orion_tpu.data import ByteTokenizer, PromptIterator, build_prompt_iterator
from orion_tpu.data.prompts import load_prompt_records, render_chat


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    ids = tok.encode("Compute 3 * 4. Answer: ")
    assert ids[0] == tok.bos_token_id
    assert tok.decode(ids) == "Compute 3 * 4. Answer: "


def test_synthetic_records_verifiable():
    recs = load_prompt_records("synthetic", synthetic_size=32, seed=1)
    assert len(recs) == 32
    for r in recs[:5]:
        expr = r["prompt"].replace("Compute ", "").split(".")[0]
        assert eval(expr) == int(r["answer"])


def test_prompt_iterator_batches_and_meta():
    it = build_prompt_iterator("synthetic", ByteTokenizer(), batch_size=4,
                               max_prompt_len=64, synthetic_size=16)
    batch = next(it)
    assert batch["prompt_ids"].shape == (4, 64)
    assert batch["prompt_lens"].min() > 0
    assert batch["answer"].shape == (4,)
    # prompts decode back to their text
    tok = ByteTokenizer()
    row = batch["prompt_ids"][0][: batch["prompt_lens"][0]]
    assert "Compute" in tok.decode(row)


def test_prompt_iterator_state_roundtrip():
    a = build_prompt_iterator("synthetic", ByteTokenizer(), 4, 64,
                              synthetic_size=10, seed=3)
    for _ in range(4):  # crosses an epoch boundary (10 records / 4)
        next(a)
    state = a.state()
    b = build_prompt_iterator("synthetic", ByteTokenizer(), 4, 64,
                              synthetic_size=10, seed=3)
    b.load_state(state)
    for _ in range(3):
        ba, bb = next(a), next(b)
        np.testing.assert_array_equal(ba["prompt_ids"], bb["prompt_ids"])


def test_offline_dataset_error_is_clear():
    with pytest.raises(RuntimeError, match="offline"):
        load_prompt_records("tldr")


def test_render_chat_fallback():
    text = render_chat(ByteTokenizer(), "hi", system="be nice")
    assert "<|system|>" in text and "<|user|>" in text
    assert text.endswith("<|assistant|>\n")


def test_launch_grpo_end_to_end(tmp_path):
    """The SPEC-config-5 CLI path: GRPO + synthetic math + rule reward,
    fully offline, with metrics and checkpoints written."""
    from orion_tpu.launch import main

    history = main([
        "grpo",
        "model.vocab_size=260", "model.hidden_size=32",
        "model.intermediate_size=64", "model.num_layers=2",
        "model.num_heads=4", "model.num_kv_heads=2", "model.dtype=float32",
        "rollout.max_new_tokens=8", "rollout.max_prompt_len=32",
        "rollout_batch_size=2", "minibatch_size=8", "group_size=4",
        "total_iterations=2", "optimizer.learning_rate=1e-4",
        f"log_dir={tmp_path}/logs", f"checkpoint_dir={tmp_path}/ckpt",
        "checkpoint_every=2", "log_every=0",
    ])
    assert len(history) == 2
    lines = open(tmp_path / "logs" / "metrics.jsonl").read().splitlines()
    rows = [json.loads(line) for line in lines]
    # the second iteration compiled nothing: the one ``setup`` row
    # follows it (ISSUE 51)
    assert [bool(r.get("setup")) for r in rows] == [False, False, True]
    assert "samples_per_sec" in rows[0] and rows[2]["total_s"] > 0
    import os

    assert os.path.isdir(tmp_path / "ckpt")


def test_launch_usage_error():
    from orion_tpu.launch import main

    with pytest.raises(SystemExit):
        main(["nope"])


def test_launch_pool_spawns_workers(tmp_path, monkeypatch):
    """async_mode + resilience.pool_size > 0: the launcher builds a
    PoolOrchestrator and spawns the rollout worker processes ITSELF
    (PR 10 satellite, ROADMAP item 1 leftover — previously only tests
    assembled the pool by hand).  Smoke: the spawn hook is replaced by
    the in-process thread harness running the REAL worker body
    (run_pool_worker), so the full wiring — config re-parse from the
    same argv, quorum wait, HELLO weights, per-worker prompt shards,
    TRAJ consumption, GOODBYE on completion, reap — runs in seconds
    without subprocess cost (the slow pool tests cover real
    processes)."""
    import threading

    import orion_tpu.launch as launch

    spawned = {}

    class _WorkerThread:
        """subprocess.Popen-shaped handle over an in-process worker."""

        def __init__(self, algo, argv, port, rank):
            cfg_cls, _ = launch.ALGOS[algo]
            cfg = launch.load_config(cfg_cls, cli_args=list(argv))
            self.result = {}

            def body():
                try:
                    self.result["sent"] = launch.run_pool_worker(
                        cfg, port, rank)
                except BaseException as e:  # surfaced by the assert
                    self.result["error"] = e

            self.thread = threading.Thread(target=body, daemon=True)
            self.thread.start()

        def wait(self, timeout=None):
            self.thread.join(timeout)

        def terminate(self):
            pass

        def kill(self):
            pass

    def fake_spawn(algo, argv, port, n):
        handles = [_WorkerThread(algo, argv, port, r) for r in range(n)]
        spawned["workers"] = handles
        return handles

    monkeypatch.setattr(launch, "spawn_pool_workers", fake_spawn)
    history = launch.main([
        "grpo",
        "model.vocab_size=260", "model.hidden_size=32",
        "model.intermediate_size=64", "model.num_layers=2",
        "model.num_heads=4", "model.num_kv_heads=2", "model.dtype=float32",
        "rollout.max_new_tokens=8", "rollout.max_prompt_len=32",
        "rollout_batch_size=2", "minibatch_size=8", "group_size=4",
        "total_iterations=3", "optimizer.learning_rate=1e-4",
        "async_mode=true", "resilience.pool_size=2",
        "resilience.heartbeat_interval=0.1",
        f"log_dir={tmp_path}/logs", "log_every=0",
    ])
    assert len(history) == 3
    workers = spawned["workers"]
    assert len(workers) == 2
    for w in workers:
        w.wait(timeout=30)
        assert not w.thread.is_alive()
        assert "error" not in w.result, w.result["error"]
    # the learner consumed real worker experience (worker ids tagged)
    assert all(np.isfinite(h["loss"]) for h in history)
    assert {h["worker"] for h in history} <= {0.0, 1.0}


def test_launch_grpo_gsm8k_fixtures(tmp_path):
    """The SPEC-config-5 CLI path on REAL-schema data: GRPO + the
    committed GSM8K fixture (data.data_dir) + the committed HF
    tokenizer + chat template + math-verifier reward — the launcher
    composes everything from flags alone."""
    import os

    from orion_tpu.launch import main

    fx = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "fixtures")
    history = main([
        "grpo",
        "model.vocab_size=512", "model.hidden_size=32",
        "model.intermediate_size=64", "model.num_layers=2",
        "model.num_heads=4", "model.num_kv_heads=2", "model.dtype=float32",
        "data.dataset=gsm8k", f"data.data_dir={fx}",
        f"data.tokenizer={os.path.join(fx, 'tokenizer')}",
        "data.use_chat_template=true", "reward=math",
        "rollout.max_new_tokens=8", "rollout.max_prompt_len=64",
        "rollout_batch_size=2", "minibatch_size=8", "group_size=4",
        "total_iterations=2", "optimizer.learning_rate=1e-4",
        f"log_dir={tmp_path}/logs", "log_every=0",
    ])
    assert len(history) == 2
    for h in history:
        assert np.isfinite(h["loss"])
        assert 0.0 <= h["reward_mean"] <= 1.0


def test_launch_ppo_with_hf_reward_model(tmp_path):
    """The SPEC-config-2 CLI path offline: reward=model:<path> loads a
    real HF sequence-classification checkpoint (built tiny with torch,
    saved safetensors), the launcher shards it on the mesh and scores
    on-device through ModelReward — config → trainer → 2 iterations."""
    torch = pytest.importorskip("torch")
    from transformers import LlamaConfig, LlamaForSequenceClassification

    from orion_tpu.launch import main

    hf_cfg = LlamaConfig(
        vocab_size=260, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=128,
        rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, attention_bias=False, num_labels=1,
        pad_token_id=0)
    torch.manual_seed(3)
    rm_dir = str(tmp_path / "rm")
    LlamaForSequenceClassification(hf_cfg).eval().save_pretrained(rm_dir)

    history = main([
        "ppo",
        "model.vocab_size=260", "model.hidden_size=32",
        "model.intermediate_size=64", "model.num_layers=2",
        "model.num_heads=4", "model.num_kv_heads=2", "model.dtype=float32",
        "share_backbone=true", f"reward=model:{rm_dir}",
        "rollout.max_new_tokens=8", "rollout.max_prompt_len=32",
        "rollout_batch_size=4", "minibatch_size=4",
        "total_iterations=2", "optimizer.learning_rate=1e-4",
        "log_every=0",
    ])
    assert len(history) == 2
    for h in history:
        assert np.isfinite(h["loss"]) and np.isfinite(h["reward_mean"])
