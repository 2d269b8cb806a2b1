"""RL substrate integration: envs, data pipeline, rollout engine, trainer."""
import numpy as np
import pytest

from repro.data import Prefetcher, PromptPipeline
from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import (
    NATGRPOTrainer, NATTrainerConfig, RolloutConfig, VOCAB_SIZE, decode_tokens,
    encode, make_env,
)
from repro.rl.env import EOS, ModArithEnv
from repro.rl.rollout import rollout_group
from repro.models import init_params, model_decl


def tiny_cfg():
    return ModelConfig(name="tiny", d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=VOCAB_SIZE,
                       blocks=dense_blocks(2), seq_parallel=False,
                       remat_policy="none", scan_layers=False)


def test_env_rewards():
    env = ModArithEnv(max_val=20, mod=97)
    rng = np.random.default_rng(0)
    p = env.sample(rng)
    full = np.array(encode(p.answer) + [EOS], np.int32)
    assert env.reward(p, full) == 1.0
    assert env.reward(p, np.array(encode("99999"), np.int32)) <= 0.2
    # partial credit for a correct prefix
    if len(p.answer) > 1:
        part = np.array(encode(p.answer[:1]), np.int32)
        assert 0 < env.reward(p, part) < 1.0


def test_tokenizer_roundtrip():
    s = "12+34%97=?"
    assert decode_tokens(encode(s)) == s


def test_pipeline_determinism_and_host_sharding():
    env = make_env("mod_arith")
    a = PromptPipeline(env, batch_size=8, max_prompt_len=24, seed=3)
    b = PromptPipeline(env, batch_size=8, max_prompt_len=24, seed=3)
    ba, bb = a.batch_at(5), b.batch_at(5)
    np.testing.assert_array_equal(ba.tokens, bb.tokens)
    # two hosts partition the same global batch
    h0 = PromptPipeline(env, batch_size=8, max_prompt_len=24, seed=3,
                        host_id=0, num_hosts=2)
    h1 = PromptPipeline(env, batch_size=8, max_prompt_len=24, seed=3,
                        host_id=1, num_hosts=2)
    g0, g1 = h0.batch_at(5), h1.batch_at(5)
    np.testing.assert_array_equal(
        np.concatenate([g0.tokens, g1.tokens]), ba.tokens)
    # checkpoint cursor roundtrip
    a.step = 17
    st = a.state_dict()
    c = PromptPipeline(env, batch_size=8, max_prompt_len=24, seed=0)
    c.load_state_dict(st)
    np.testing.assert_array_equal(next(c).tokens, a.batch_at(17).tokens)


def test_iter_prompts_streams_batches_without_advancing_cursor():
    """iter_prompts yields the same prompts batch_at produces, unpadded, and
    leaves the pipeline cursor untouched (checkpoint resume unaffected)."""
    env = make_env("mod_arith")
    pipe = PromptPipeline(env, batch_size=4, max_prompt_len=24, seed=7)
    stream = pipe.iter_prompts()
    got = [next(stream) for _ in range(10)]  # spans three batches
    assert pipe.step == 0
    for j, (prompt, toks, n) in enumerate(got):
        ref = pipe.batch_at(j // 4)
        i = j % 4
        assert n == int(ref.prompt_lens[i])
        np.testing.assert_array_equal(toks, ref.tokens[i, :n])
        assert prompt.answer == ref.prompts[i].answer


def test_prefetcher():
    out = list(Prefetcher(iter(range(7)), depth=2))
    assert out == list(range(7))

    def boom():
        yield 1
        raise RuntimeError("boom")

    it = Prefetcher(boom(), depth=1)
    assert next(it) == 1
    with pytest.raises(RuntimeError):
        for _ in it:
            pass


def test_rollout_shapes_and_masks(key):
    cfg = tiny_cfg()
    params = init_params(key, model_decl(cfg))
    env = make_env("mod_arith")
    pipe = PromptPipeline(env, batch_size=3, max_prompt_len=16)
    pb = next(pipe)
    rcfg = RolloutConfig(max_new_tokens=8, group_size=4, overprovision=1.5)
    rb = rollout_group(params, cfg, rcfg, pb.tokens, pb.prompt_lens, key)
    b = 3 * 4
    assert rb.tokens.shape == (b, 16 + 8)
    assert rb.response_mask.shape == rb.tokens.shape
    # responses start exactly at prompt_lens and run response_lens tokens
    for i in range(b):
        pl, rl = int(rb.prompt_lens[i]), int(rb.response_lens[i])
        row = rb.response_mask[i]
        assert row[:pl].sum() == 0
        assert row[pl:pl + rl].sum() == rl
        assert row[pl + rl:].sum() == 0
        # behaviour logp only on response tokens, <= 0
        assert np.all(rb.old_logp[i][row == 0] == 0)
        assert np.all(rb.old_logp[i][row == 1] <= 1e-5)


def test_trainer_selectors_one_step():
    cfg = tiny_cfg()
    for sel, kw in [("rpc", (("min_cut", 4),)), ("urs", (("p", 0.5),)),
                    ("full", ()), ("det_trunc", ()), ("entropy", ())]:
        tc = NATTrainerConfig(
            selector=sel, selector_kwargs=kw, prompts_per_step=2,
            max_prompt_len=16,
            rollout=RolloutConfig(max_new_tokens=8, group_size=4),
            adamw=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20),
            bucket_align=8, seed=0)
        tr = NATGRPOTrainer(cfg, tc)
        m = tr.train_step()
        assert np.isfinite(m["loss"]), sel
        assert 0 < m["selected_ratio"] <= 1.0 + 1e-6, sel
        if sel == "det_trunc":
            assert m["bucket_len"] <= 16 + 8


def test_rpc_repack_shrinks_learner_tokens():
    """With long responses, RPC's physical repack processes fewer learner
    tokens than full-token GRPO on the same rollouts."""
    cfg = tiny_cfg()
    common = dict(prompts_per_step=2, max_prompt_len=16,
                  rollout=RolloutConfig(max_new_tokens=32, group_size=4,
                                        eos_id=-1),  # never stop early
                  adamw=AdamWConfig(lr=1e-4, warmup_steps=2, total_steps=10),
                  bucket_align=8, seed=1)
    full = NATGRPOTrainer(cfg, NATTrainerConfig(selector="full", **common))
    rpc = NATGRPOTrainer(cfg, NATTrainerConfig(
        selector="rpc", selector_kwargs=(("min_cut", 2),), **common))
    mf = full.train_step()
    toks_rpc = [rpc.train_step()["tokens_scored"] for _ in range(6)]
    assert np.mean(toks_rpc) < mf["tokens_scored"]
