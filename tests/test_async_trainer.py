"""Stream-overlapped trainer (DESIGN.md §6): token-exact serial parity at
max_staleness=0, the sample queue's staleness contract, importance-correction
metrics under forced staleness, and quiesce-checkpoint resume.  Plus the
multi-producer reassembly contract (DESIGN.md §12): N racing producers,
ordered delivery, first-error-wins failure, deadlock-free reservations."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.grpo import group_advantages
from repro.core.repack import bucket_ladder, pick_bucket
from repro.core.selectors import make_selector
from repro.data import PromptPipeline
from repro.models import init_params, model_decl
from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import (
    AsyncNATGRPOTrainer,
    ContinuousRolloutEngine,
    EngineConfig,
    NATGRPOTrainer,
    NATTrainerConfig,
    RolloutConfig,
    SampleQueue,
    TaggedGroup,
    VOCAB_SIZE,
    make_env,
    make_train_step,
    rollout_group_continuous,
)


def tiny_cfg():
    return ModelConfig(name="tiny", d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=VOCAB_SIZE,
                       blocks=dense_blocks(2), seq_parallel=False,
                       remat_policy="none", scan_layers=False)


def trainer_cfg(**kw):
    base = dict(
        selector="rpc", selector_kwargs=(("min_cut", 4),),
        prompts_per_step=2, max_prompt_len=16,
        rollout=RolloutConfig(max_new_tokens=8, group_size=4,
                              overprovision=1.5),
        steps_per_sync=2,
        adamw=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        bucket_align=8, seed=0)
    base.update(kw)
    return NATTrainerConfig(**base)


def serial_reference_run(cfg, tc, num_steps):
    """Independent re-implementation of the historical serial train loop
    (pre-async-refactor NATGRPOTrainer.train_step), built from the same
    primitives: the parity oracle for the staleness-0 pipeline."""
    env = make_env(tc.env, **dict(tc.env_kwargs))
    pipeline = PromptPipeline(env, batch_size=tc.prompts_per_step,
                              max_prompt_len=tc.max_prompt_len, seed=tc.seed)
    key = jax.random.PRNGKey(tc.seed)
    key, k = jax.random.split(key)
    params = init_params(k, model_decl(cfg))
    from repro.optim.adamw import init_opt_state

    opt_state = init_opt_state(params, tc.adamw)
    selector = make_selector(tc.selector, **dict(tc.selector_kwargs))
    engine = ContinuousRolloutEngine(
        cfg, tc.rollout, EngineConfig(
            num_slots=tc.num_slots
            or tc.prompts_per_step * tc.rollout.group_size,
            max_prompt_len=tc.max_prompt_len,
            steps_per_sync=tc.steps_per_sync))
    train_step = jax.jit(make_train_step(cfg, tc.grpo, tc.adamw,
                                         vocab_chunks=1))
    t_max = tc.max_prompt_len + tc.rollout.max_new_tokens
    ladder = bucket_ladder(t_max, tc.num_buckets, tc.bucket_align)

    p, g = tc.prompts_per_step, tc.rollout.group_size
    steps = []
    for _ in range(num_steps):
        pb = next(pipeline)
        key, k_roll, k_sel = jax.random.split(key, 3)
        rb = rollout_group_continuous(
            params, cfg, tc.rollout, pb.tokens, pb.prompt_lens, k_roll,
            engine=engine)
        rewards = np.zeros((p, g), np.float32)
        for i in range(p):
            for j in range(g):
                r = i * g + j
                pl, rl = int(rb.prompt_lens[r]), int(rb.response_lens[r])
                rewards[i, j] = env.reward(pb.prompts[i],
                                           rb.tokens[r, pl:pl + rl])
        adv = np.asarray(group_advantages(jnp.asarray(rewards),
                                          tc.grpo.adv_eps)).reshape(-1)
        sel = selector(k_sel, jnp.asarray(rb.response_mask))
        batch = {
            "tokens": rb.tokens,
            "response_mask": rb.response_mask,
            "old_logp": rb.old_logp,
            "advantages": adv.astype(np.float32),
            "ht_weights": np.asarray(sel.ht_weights, np.float32),
            "orig_lengths": rb.response_lens.astype(np.float32),
            "lengths": (rb.prompt_lens + rb.response_lens).astype(np.int32),
            "behavior_logp": rb.old_logp,
            "staleness": np.zeros((rb.tokens.shape[0],), np.float32),
        }
        if tc.repack and sel.prefix_structured:
            keep_total = rb.prompt_lens + np.minimum(
                np.asarray(sel.keep_len), rb.response_lens)
            t_new = min(pick_bucket(int(keep_total.max()), ladder),
                        rb.tokens.shape[1])
            batch = {k: (v[:, :t_new] if getattr(v, "ndim", 0) >= 2 else v)
                     for k, v in batch.items()}
            batch["lengths"] = keep_total.astype(np.int32)
        params, opt_state, metrics = train_step(
            params, opt_state, {k: jnp.asarray(v) for k, v in batch.items()})
        steps.append({
            "tokens": np.asarray(batch["tokens"]).copy(),
            "loss": float(metrics["loss"]),
            "reward_mean": float(rewards.mean()),
        })
    return params, steps


def test_staleness0_token_and_metric_exact():
    """The async pipeline at max_staleness=0 reproduces the serial loop
    token-for-token (learner batches), metric-for-metric (loss, rewards),
    and parameter-for-parameter (bitwise after N updates)."""
    cfg, tc = tiny_cfg(), trainer_cfg()
    n = 3
    ref_params, ref_steps = serial_reference_run(cfg, tc, n)

    tr = NATGRPOTrainer(cfg, tc)
    consumed = []
    orig_pop = tr.queue.pop

    def spy_pop(version, timeout=None):
        g = orig_pop(version, timeout=timeout)
        consumed.append(g)
        return g

    tr.queue.pop = spy_pop
    metrics = [tr.train_step() for _ in range(n)]
    tr.close()

    for i in range(n):
        assert metrics[i]["staleness"] == 0
        # the learner consumed exactly the serial rollout's token grid
        rb = consumed[i].batch
        b = ref_steps[i]["tokens"].shape[0]
        assert rb.tokens.shape[0] == b
        np.testing.assert_array_equal(
            rb.tokens[:, :ref_steps[i]["tokens"].shape[1]],
            ref_steps[i]["tokens"])
        assert metrics[i]["loss"] == ref_steps[i]["loss"]
        assert metrics[i]["reward_mean"] == ref_steps[i]["reward_mean"]
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        tr.params, ref_params)


def _dummy_group(version, index=0):
    return TaggedGroup(index=index, behavior_version=version, batch=None,
                       prompt_batch=None, key_sel=None, t_rollout=0.0)


def test_sample_queue_staleness_contract():
    """pop() never serves a group staler than max_staleness versions: the
    over-stale head is dropped (and counted), fresh groups still flow."""
    q = SampleQueue(capacity=4, max_staleness=1)
    q.put(_dummy_group(version=0, index=0))
    assert q.pop(current_version=1).behavior_version == 0  # staleness 1: ok

    q.put(_dummy_group(version=0, index=1))
    q.put(_dummy_group(version=2, index=2))
    g = q.pop(current_version=3)  # v0 is 3 stale -> dropped, v2 served
    assert g.behavior_version == 2
    assert q.dropped_stale == 1

    with pytest.raises(TimeoutError):
        q.pop(current_version=3, timeout=0.05)


def test_sample_queue_propagates_actor_errors():
    q = SampleQueue(capacity=1, max_staleness=0)
    q.fail(RuntimeError("actor died"))
    with pytest.raises(RuntimeError, match="actor died"):
        q.pop(current_version=0, timeout=1.0)


def test_sample_queue_fail_first_error_wins():
    """A second fail() (e.g. close()'s poison pill racing a real actor
    crash) must not mask the original exception — regression for the
    fail/put race that used to surface the *last* error."""
    q = SampleQueue(capacity=1, max_staleness=0)
    q.put(_dummy_group(version=0, index=0))  # full: next put blocks

    raised = []

    def blocked_put():
        try:
            q.put(_dummy_group(version=0, index=1), timeout=30.0)
        except BaseException as e:  # noqa: BLE001 - recording for assert
            raised.append(e)

    t = threading.Thread(target=blocked_put, daemon=True)
    t.start()
    time.sleep(0.2)  # let the put block on the full queue
    q.fail(RuntimeError("root cause"))
    q.fail(RuntimeError("poison pill"))
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert len(raised) == 1 and str(raised[0]) == "root cause"
    with pytest.raises(RuntimeError, match="root cause"):
        q.pop(current_version=0, timeout=1.0)  # consumer sees it too


def test_sample_queue_reassembles_index_order():
    """Out-of-order deposits from racing producers are served in serial
    index order, and a reserved gap holds younger groups back."""
    q = SampleQueue(capacity=4, max_staleness=3)
    q.reserve(0)
    q.put(_dummy_group(version=0, index=2), producer="f1")
    q.put(_dummy_group(version=1, index=1), producer="f1")
    with pytest.raises(TimeoutError):
        q.pop(current_version=1, timeout=0.05)  # index 0 still in flight
    q.put(_dummy_group(version=1, index=0), producer="f0")
    got = [q.pop(current_version=1).index for _ in range(3)]
    assert got == [0, 1, 2]
    assert q.watermarks == {"f0": 1, "f1": 1}


def test_sample_queue_cancel_unblocks_gap():
    """A producer abandoning its reservation (rollout raised) must not
    wedge the consumer waiting on the gap."""
    q = SampleQueue(capacity=4, max_staleness=0)
    q.reserve(0)
    q.put(_dummy_group(version=0, index=1))
    q.cancel(0)
    assert q.pop(current_version=0, timeout=5.0).index == 1
    assert q.inflight() == 0


# --- multi-producer property ---


@settings(max_examples=15, deadline=None)
@given(num_producers=st.integers(1, 4), max_staleness=st.integers(0, 3),
       num_groups=st.integers(4, 14), drop_mod=st.integers(0, 5),
       seed=st.integers(0, 999))
def test_sample_queue_multi_producer_property(num_producers, max_staleness,
                                              num_groups, drop_mod, seed):
    """N producers race the trainer's claim/reserve/roll/put protocol while
    a learner pops and bumps its version; some claims are abandoned
    (cancel).  Invariants: delivery is the serial index order minus the
    abandoned indices, nothing served is staler than ``max_staleness``,
    and the system quiesces — no deadlock, no leaked reservations."""
    import random

    rng = random.Random(seed)
    q = SampleQueue(capacity=max_staleness + 1, max_staleness=max_staleness)
    lock = threading.Lock()
    state = {"next": 0, "version": 0}
    dropped, errors = set(), []

    def producer(name):
        try:
            while True:
                with lock:
                    i = state["next"]
                    if i >= num_groups:
                        return
                    # the trainer's staleness gate: claim only when the
                    # learner is close enough, reserve INSIDE the claim
                    # lock so the queue knows the gap before anyone
                    # younger deposits.  Cancelled indices never reach the
                    # learner, so the gate counts them as consumed —
                    # otherwise a drop wedges it permanently.
                    gated = (i - state["version"] - len(dropped)
                             > max_staleness)
                    if not gated:
                        state["next"] = i + 1
                        version = state["version"]
                        q.reserve(i, timeout=30.0)
                if gated:
                    time.sleep(0.001)
                    continue
                time.sleep(rng.random() * 0.003)  # racy rollout
                if drop_mod and i % drop_mod == drop_mod - 1:
                    with lock:
                        dropped.add(i)
                    q.cancel(i)
                    continue
                q.put(_dummy_group(version=version, index=i),
                      timeout=30.0, producer=name)
        except BaseException as e:  # noqa: BLE001 - surface in main thread
            errors.append(e)
            q.fail(e)

    threads = [threading.Thread(target=producer, args=(f"p{k}",),
                                daemon=True)
               for k in range(num_producers)]
    for t in threads:
        t.start()

    served = []
    while True:
        with lock:
            done = (state["next"] >= num_groups and q.inflight() == 0
                    and q.qsize() == 0)
        if done:
            break
        try:
            g = q.pop(state["version"], timeout=0.2)
        except TimeoutError:
            continue
        served.append(g)
        with lock:
            state["version"] += 1
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive(), "producer deadlocked"
    assert not errors, errors

    expect = [i for i in range(num_groups) if i not in dropped]
    assert [g.index for g in served] == expect, "serial order violated"
    # the gate bounds staleness at claim time and drops known then are a
    # subset of drops below the index, so nothing ever goes over-stale:
    # the queue must have served everything within the bound, dropped none
    for pos, g in enumerate(served):
        assert pos - g.behavior_version <= max_staleness
    assert q.dropped_stale == 0
    assert q.inflight() == 0 and q.qsize() == 0


@pytest.mark.parametrize("overprovision", [1.0, 1.5])
def test_forced_staleness_importance_metrics(overprovision):
    """With max_staleness=1 and a held learner, the second group is
    guaranteed one version stale: its step must report the truncated-IS
    correction metrics and stay finite."""
    cfg = tiny_cfg()
    tc = trainer_cfg(
        max_staleness=1,
        rollout=RolloutConfig(max_new_tokens=8, group_size=4,
                              overprovision=overprovision))
    tr = AsyncNATGRPOTrainer(cfg, tc)
    try:
        tr._ensure_actor()
        # both groups roll under version 0 before the learner moves
        deadline = time.monotonic() + 120
        while tr.queue.qsize() < 2:
            assert time.monotonic() < deadline, "actor stalled"
            time.sleep(0.01)
        m0 = tr.train_step()
        m1 = tr.train_step()
    finally:
        tr.close()

    assert m0["staleness"] == 0 and m0["stale_frac"] == 0.0
    assert m1["staleness"] == 1 and m1["stale_frac"] == 1.0
    assert m1["behavior_version"] == 0 and m1["policy_version"] == 2
    assert np.isfinite(m1["loss"])
    assert m1["is_ratio_mean"] > 0.0
    assert 0.0 <= m1["is_clip_frac"] <= 1.0
    assert m1["dropped_stale"] == 0


def test_streaming_rollout_stats_accounting():
    """Streaming groups surface the rollout token cost: generated tokens
    never exceed the budget, utilization stays in (0, 1]."""
    cfg = tiny_cfg()
    tc = trainer_cfg(max_staleness=2)
    tr = AsyncNATGRPOTrainer(cfg, tc)
    try:
        ms = [tr.train_step() for _ in range(3)]
    finally:
        tr.close()
    for m in ms:
        assert m["tokens_budget"] == 2 * 6 * 8
        assert 0 < m["tokens_generated"] <= m["tokens_budget"]
        assert m["staleness"] <= 2


@pytest.mark.slow
def test_quiesce_checkpoint_resume_exact(tmp_path):
    """save_checkpoint quiesces at a group boundary; a fresh trainer that
    restores it continues the exact parameter stream."""
    from repro.checkpoint import CheckpointManager

    cfg, tc = tiny_cfg(), trainer_cfg()
    mgr = CheckpointManager(str(tmp_path), keep_last=2)

    a = NATGRPOTrainer(cfg, tc)
    a.train_step()
    a.train_step()
    saved = a.save_checkpoint(mgr)
    assert mgr.latest_step() == saved
    while a.step_count < saved + 2:
        a.train_step()
    a.close()

    b = NATGRPOTrainer(cfg, tc)
    extra = b.restore_checkpoint(mgr)
    assert b.step_count == saved == int(extra["learner_version"])
    b.train_step()
    b.train_step()
    b.close()

    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)),
        a.params, b.params)
