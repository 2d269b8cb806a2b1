"""Chaos-hardened elastic fleets (DESIGN.md §13): failure detection,
token-exact group reclaim, and the fault-injection harness.

The load-bearing gates:

* **kill-one-replica recovery** — a fleet of 2 with one replica killed by
  an injected death produces per-group tokens identical to the no-fault
  fleet: the reclaimed index re-derives the dead claimer's exact keys
  from the shared KeyChain;
* **property test** — random seeded fault schedules (kills, stalls,
  put-failures across N replicas) either complete token-exactly or raise
  a clean structured ``SupervisorError``; never a deadlock, never a lost
  or double-consumed group;
* **dead-producer unblock** — removing a dead producer's watermark and
  cancelling its orphaned reservations lets a blocked ``pop`` proceed.

Fast tests drive the real trainer orchestration with a *fake* per-group
roll (``_roll_group`` overridden with a pure function of the chain keys):
the claim/reserve/reclaim/deposit concurrency under test is byte-for-byte
the production path, only the jax compute is skipped.  The slow tests at
the bottom run real engines end to end.
"""
import threading
import time
import types

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.dist import PublicationError, WeightPublisher
from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import (
    DistNATGRPOTrainer,
    NATTrainerConfig,
    QuiesceTimeout,
    RetryPolicy,
    ReplicaSupervisor,
    RolloutConfig,
    SampleQueue,
    SupervisorError,
    TaggedGroup,
    VOCAB_SIZE,
    retry_call,
)
from repro.testing import FaultPlan, FaultSpec, InjectedActorDeath, InjectedFault


def tiny_cfg(**kw):
    base = dict(name="tiny", d_model=64, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128, vocab_size=VOCAB_SIZE,
                blocks=dense_blocks(2), seq_parallel=False,
                remat_policy="none", scan_layers=False)
    base.update(kw)
    return ModelConfig(**base)


def fleet_cfg(**kw):
    base = dict(
        selector="rpc", selector_kwargs=(("min_cut", 4),),
        prompts_per_step=2, max_prompt_len=16,
        rollout=RolloutConfig(max_new_tokens=8, group_size=4,
                              overprovision=1.5, temperature=1.0),
        steps_per_sync=2,
        adamw=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        bucket_align=8, num_buckets=1, seed=0,
        supervise=True, supervise_interval=0.02)
    base.update(kw)
    return NATTrainerConfig(**base)


def _fake_tokens(i, k_roll):
    """The fake roll's output: a pure function of (index, chain key) — two
    claimers of the same index must produce identical 'tokens'."""
    return np.asarray(k_roll).astype(np.int64) + i


class _FakeRollFleet(DistNATGRPOTrainer):
    """Fleet trainer whose per-group roll is the cheap pure function above:
    the claim/reserve/reclaim/deposit protocol is the production code, the
    jax rollout is not exercised (keeps chaos examples sub-second)."""

    def _roll_group(self, engine, params, pb, k_roll, i):
        time.sleep(0.01)  # widen the race window between replicas
        return types.SimpleNamespace(tokens=_fake_tokens(i, k_roll))


def _collect(tr, k, timeout=60.0):
    got = {}
    tr._ensure_actor()
    while len(got) < k:
        g = tr.queue.pop(0, timeout=timeout)
        assert g.index not in got, f"group {g.index} served twice"
        got[g.index] = g
    return got


def _wait_for(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        if time.monotonic() > deadline:
            pytest.fail(f"timed out waiting for {msg}")
        time.sleep(0.01)


# --------------------------------------------------------- chaos harness
def test_fault_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        FaultSpec(site="actor", kind="explode")
    with pytest.raises(ValueError, match="delay"):
        FaultSpec(site="actor", kind="stall")
    FaultSpec(site="actor", kind="stall", delay=0.1)  # ok


def test_fault_plan_matching_after_times_replica_at():
    plan = FaultPlan([
        FaultSpec(site="actor", replica="r1", at=2, after=1, times=2,
                  exc=InjectedActorDeath),
    ])
    # wrong site / replica / index: pass through
    plan.fire("queue_put", replica="r1", index=2)
    plan.fire("actor", replica="r0", index=2)
    plan.fire("actor", replica="r1", index=3)
    assert plan.total_fired() == 0
    # first matching occurrence is skipped by after=1
    plan.fire("actor", replica="r1", index=2)
    assert plan.total_fired() == 0 and not plan.exhausted()
    # then fires exactly `times` times
    for _ in range(2):
        with pytest.raises(InjectedActorDeath, match="replica=r1"):
            plan.fire("actor", replica="r1", index=2)
    plan.fire("actor", replica="r1", index=2)  # budget exhausted: pass
    assert plan.fired == {"actor": 2}
    assert plan.total_fired() == 2 and plan.exhausted()


def test_fault_plan_stall_sleeps_not_raises():
    plan = FaultPlan([FaultSpec(site="drive", kind="stall", delay=0.1)])
    t0 = time.monotonic()
    plan.fire("drive")           # stalls
    assert time.monotonic() - t0 >= 0.09
    t0 = time.monotonic()
    plan.fire("drive")           # budget spent: pass-through
    assert time.monotonic() - t0 < 0.05
    assert plan.fired == {"drive": 1}


def test_fault_plan_random_is_deterministic():
    a = FaultPlan.random(7, replicas=["fleet0", "fleet1"])
    b = FaultPlan.random(7, replicas=["fleet0", "fleet1"])
    assert [dataclass_tuple(s) for s in a.specs] \
        == [dataclass_tuple(s) for s in b.specs]
    c = FaultPlan.random(8, replicas=["fleet0", "fleet1"])
    assert len(c.specs) != len(a.specs) or (
        [dataclass_tuple(s) for s in c.specs]
        != [dataclass_tuple(s) for s in a.specs]) or not a.specs


def dataclass_tuple(s):
    return (s.site, s.kind, s.replica, s.at, s.after, s.times, s.delay,
            s.exc.__name__)


# ------------------------------------------------------- bounded retries
def test_retry_call_bounded_and_escalates():
    calls, retries = [], []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise InjectedFault("transient")
        return "ok"

    out = retry_call(flaky, RetryPolicy(max_attempts=3, backoff_s=0.001),
                     (InjectedFault,),
                     lambda attempt, exc: retries.append(attempt))
    assert out == "ok" and len(calls) == 3 and retries == [1, 2]

    # exhausting the budget re-raises the last retryable error
    with pytest.raises(InjectedFault):
        retry_call(lambda: (_ for _ in ()).throw(InjectedFault("x")),
                   RetryPolicy(max_attempts=2, backoff_s=0.001),
                   (InjectedFault,))

    # non-retryable escalates immediately (one attempt)
    calls.clear()

    def wrong():
        calls.append(1)
        raise KeyError("not transient")

    with pytest.raises(KeyError):
        retry_call(wrong, RetryPolicy(max_attempts=5, backoff_s=0.001),
                   (InjectedFault,))
    assert len(calls) == 1


def test_publisher_retries_transient_and_escalates_persistent():
    params = {"w": np.ones((4, 4), np.float32)}
    dev = jax.devices()[0]

    pub = WeightPublisher({"fleet0": dev}, max_attempts=3, backoff_s=0.001)
    pub.chaos = FaultPlan([FaultSpec(site="publish", at=1)])
    pub.publish(params, epoch=0)                  # clean
    out = pub.publish(params, epoch=1)            # one injected failure
    assert set(out) == {"fleet0"}
    assert pub.stats["publish_retries"] == 1
    assert pub.stats["epoch"] == 1 and pub.stats["publishes"] == 2

    pub2 = WeightPublisher({"fleet0": dev}, max_attempts=3, backoff_s=0.001)
    pub2.chaos = FaultPlan([FaultSpec(site="publish", times=99)])
    with pytest.raises(PublicationError, match="after 3 attempts"):
        pub2.publish(params, epoch=0)
    assert pub2.stats["publish_retries"] == 2     # bounded, then escalate
    assert pub2.stats["publishes"] == 0


def test_publisher_add_remove_target():
    params = {"w": np.ones((2, 2), np.float32)}
    dev = jax.devices()[0]
    pub = WeightPublisher({"fleet0": dev})
    pub.publish(params, epoch=3)
    tree = pub.add_target("fleet1", dev, params=params, epoch=3)
    assert tree is not None
    _, epoch = pub.latest("fleet1")
    assert epoch == 3
    with pytest.raises(ValueError, match="already registered"):
        pub.add_target("fleet1", dev)
    pub.remove_target("fleet1")
    with pytest.raises(KeyError):
        pub.latest("fleet1")


# --------------------------------------------------- queue-level recovery
def _group(i, version=0):
    return TaggedGroup(index=i, behavior_version=version, batch=None,
                       prompt_batch=None, key_sel=jax.random.PRNGKey(i),
                       t_rollout=0.0)


def test_queue_remove_producer_unblocks_pop():
    """Regression: a dead producer's reservation used to wedge pop forever
    (the queue held younger groups for a gap nobody would ever fill)."""
    q = SampleQueue(capacity=4, max_staleness=99)
    q.reserve(0)                            # dead producer's claim
    q.put(_group(1), producer="b")
    q.watermarks["a"] = 0                   # its earlier deposit's watermark
    with pytest.raises(TimeoutError):
        q.pop(0, timeout=0.2)               # index 0 gap blocks the head
    q.remove_producer("a", cancel=(0,))
    assert q.pop(0, timeout=5.0).index == 1
    assert "a" not in q.watermarks and "b" in q.watermarks


def test_queue_drops_duplicate_deposits():
    """At-most-once per index: a condemned replica waking up late and
    re-depositing a reclaimed (or already-served) group is dropped."""
    q = SampleQueue(capacity=4, max_staleness=99)
    q.reserve(0)
    q.put(_group(0), producer="a")          # survivor's re-roll lands first
    q.put(_group(0), producer="b")          # late duplicate while queued
    assert q.dropped_dup == 1 and q.qsize() == 1
    assert q.pop(0, timeout=5.0).index == 0
    q.put(_group(0), producer="b")          # duplicate of a served index
    assert q.dropped_dup == 2 and q.qsize() == 0
    # a stale reservation attached to the duplicate is released too
    q.reserve(0)
    q.put(_group(0), producer="b")
    assert q.inflight() == 0


# ------------------------------------------------------------ supervisor
def test_supervisor_detects_death_reclaims_and_dewatermarks():
    q = SampleQueue(capacity=4, max_staleness=99)
    sup = ReplicaSupervisor(q, hang_timeout=5.0, interval=0.02)
    die = threading.Event()
    victim = threading.Thread(target=die.wait, daemon=True)
    survivor = threading.Thread(target=lambda: time.sleep(30), daemon=True)
    victim.start(), survivor.start()
    sup.register("a", thread=victim)
    sup.register("b", thread=survivor)
    q.reserve(3)
    sup.claim("a", 3)
    q.watermarks["a"] = 0                   # deposit-then-die: ghost entry
    sup.start()
    try:
        die.set()                           # the thread exits silently
        _wait_for(lambda: sup.stats["replicas_failed"] == 1,
                  msg="death detection")
        assert sup.stats["groups_reclaimed"] == 1
        assert "a" not in q.watermarks      # ghost watermark removed
        assert q.inflight() == 1            # reservation SURVIVES for reclaim
        assert sup.should_stop("a") and not sup.should_stop("b")
        assert sup.reclaim_pending()
        assert sup.take_reclaim("b") == 3   # survivor adopts the orphan
        assert sup.take_reclaim("b") is None
        snap = {s.name: s for s in sup.status()}
        assert snap["a"].dead and not snap["b"].dead
        assert "state=dead" in snap["a"].describe()
        assert snap["b"].claimed == 3       # take_reclaim assigned it
    finally:
        sup.stop()


def test_supervisor_tolerates_registered_but_unstarted_thread():
    """Join-race regression: replicas register BEFORE their thread starts
    (so the first heartbeat/claim always finds them), and the monitor
    must not book the not-yet-started thread (is_alive() False, ident
    None) as dead-without-reporting."""
    q = SampleQueue(capacity=4, max_staleness=99)
    sup = ReplicaSupervisor(q, hang_timeout=5.0, interval=0.01)
    go = threading.Event()
    t = threading.Thread(target=go.wait, daemon=True)
    sup.register("late", thread=t)      # registered, NOT started
    sup.start()
    try:
        time.sleep(0.1)                 # many monitor polls
        assert sup.stats["replicas_failed"] == 0
        assert not sup.should_stop("late")
        t.start()                       # now it lives...
        time.sleep(0.05)
        assert sup.stats["replicas_failed"] == 0
        go.set()                        # ...and exits silently -> dead
        _wait_for(lambda: sup.stats["replicas_failed"] == 1,
                  msg="death detection after a real start+exit")
    finally:
        sup.stop()


def test_supervisor_hang_detection_respects_progress_watermark():
    q = SampleQueue(capacity=4, max_staleness=99)
    prog = {"v": 0}
    sup = ReplicaSupervisor(q, hang_timeout=0.5, interval=0.02)
    t = threading.Thread(target=lambda: time.sleep(30), daemon=True)
    t.start()
    sup.register("w", thread=t, progress=lambda: prog["v"])
    q.reserve(2)
    sup.claim("w", 2)
    sup.start()
    try:
        # a long-but-ADVANCING rollout is never condemned: the progress
        # watermark refreshes activity even with no explicit heartbeat
        for _ in range(14):
            prog["v"] += 1
            time.sleep(0.05)
        assert sup.stats["replicas_condemned"] == 0
        # freeze the watermark: now it is a hang
        _wait_for(lambda: sup.stats["replicas_condemned"] == 1,
                  msg="hang condemnation")
        assert sup.take_reclaim("other") == 2
        # all replicas condemned -> the queue is failed with a structured
        # error naming the victim (first-error-wins on the consumer side)
        with pytest.raises(SupervisorError, match="dead or condemned"):
            q.pop(0, timeout=5.0)
    finally:
        sup.stop()


def test_supervisor_all_dead_fails_queue_with_statuses():
    q = SampleQueue(capacity=2, max_staleness=99)
    sup = ReplicaSupervisor(q, hang_timeout=5.0, interval=0.02)
    t = threading.Thread(target=lambda: None)
    t.start(), t.join()
    sup.register("solo", thread=t)
    sup.report_failure("solo", InjectedActorDeath("boom"))
    assert sup.all_dead()
    with pytest.raises(SupervisorError) as ei:
        q.pop(0, timeout=5.0)
    err = ei.value
    assert "all fleet replicas" in str(err)
    assert [s.name for s in err.statuses] == ["solo"]
    assert err.statuses[0].dead
    assert "InjectedActorDeath" in err.statuses[0].describe()
    # first error wins: a later poison pill never masks the root cause
    q.fail(RuntimeError("trainer closed"))
    with pytest.raises(SupervisorError):
        q.pop(0, timeout=5.0)


# --------------------------------------- fleet recovery (fake roll, fast)
def test_fleet2_kill_one_token_exact_fake_roll():
    """An injected actor death after fleet1's claim: the supervisor
    reclaims its group, fleet0 re-rolls it off the shared chain, and every
    delivered group matches the chain oracle exactly."""
    k = 4
    plan = FaultPlan([FaultSpec(site="actor", replica="fleet1",
                                exc=InjectedActorDeath)])
    tr = _FakeRollFleet(tiny_cfg(), fleet_cfg(fleet=2, max_staleness=k),
                        chaos=plan)
    try:
        oracle = {i: _fake_tokens(i, tr._key_chain.keys_for(i)[1])
                  for i in range(k)}
        got = _collect(tr, k)
        assert sorted(got) == list(range(k))
        for i in range(k):
            np.testing.assert_array_equal(got[i].batch.tokens, oracle[i])
        stats = tr.publication_stats()
        sup = stats["supervisor"]
        assert sup["replicas_failed"] == 1
        assert sup["groups_reclaimed"] == 1   # death fires after the claim
        assert plan.exhausted()
        assert "fleet1" not in stats["watermarks"]
    finally:
        tr.close()


def test_fleet2_stall_condemned_then_duplicate_dropped():
    """A stalled replica is condemned past hang_timeout, its group is
    re-rolled by the survivor; when the stalled thread wakes its late
    deposit is dropped as a duplicate and its loop exits."""
    k = 4
    plan = FaultPlan([FaultSpec(site="actor", kind="stall", delay=1.5,
                                replica="fleet1")])
    tr = _FakeRollFleet(
        tiny_cfg(), fleet_cfg(fleet=2, max_staleness=k, hang_timeout=0.3),
        chaos=plan)
    try:
        oracle = {i: _fake_tokens(i, tr._key_chain.keys_for(i)[1])
                  for i in range(k)}
        got = _collect(tr, k)
        for i in range(k):
            np.testing.assert_array_equal(got[i].batch.tokens, oracle[i])
        sup = tr.supervisor.stats
        assert sup["replicas_condemned"] == 1
        assert sup["groups_reclaimed"] == 1
        # exactly one of the two deposits for the stalled index survives
        _wait_for(lambda: tr.queue.dropped_dup == 1,
                  msg="late duplicate deposit")
    finally:
        tr.close()


def test_elastic_replacement_after_death():
    """Kill one of two replicas, join a replacement mid-run: the newcomer
    gets the current publication epoch, claims from a clean boundary, and
    the stream stays token-exact throughout."""
    plan = FaultPlan([FaultSpec(site="actor", replica="fleet1",
                                exc=InjectedActorDeath)])
    tr = _FakeRollFleet(tiny_cfg(), fleet_cfg(fleet=2, max_staleness=8),
                        chaos=plan)
    try:
        oracle = {i: _fake_tokens(i, tr._key_chain.keys_for(i)[1])
                  for i in range(8)}
        got = _collect(tr, 3)
        _wait_for(lambda: tr.supervisor.stats["replicas_failed"] == 1,
                  msg="injected death")
        name = tr.add_replica()
        assert name == "fleet2"
        _, epoch = tr.publisher.latest("fleet2")
        assert epoch == tr._learner_version        # current epoch, no wait
        got.update(_collect(tr, 5))     # five MORE groups: 3..7
        assert sorted(got) == list(range(8))
        for i in range(8):
            np.testing.assert_array_equal(got[i].batch.tokens, oracle[i])
        sup = tr.supervisor.stats
        assert sup["joins"] == 1 and sup["replicas_failed"] == 1
        assert set(tr.queue.watermarks) <= {"fleet0", "fleet2"}
    finally:
        tr.close()


def test_quiesce_timeout_names_replica_watermark_heartbeat():
    """A wedged quiesce raises a structured QuiesceTimeout naming each
    replica's state, claimed group, queue watermark, and heartbeat age."""
    plan = FaultPlan([FaultSpec(site="actor", kind="stall", delay=1.5,
                                replica="fleet0")])
    tr = _FakeRollFleet(tiny_cfg(), fleet_cfg(fleet=1, max_staleness=2),
                        chaos=plan)
    try:
        tr._ensure_actor()
        _wait_for(lambda: plan.total_fired() == 1, msg="stall injection")
        with pytest.raises(QuiesceTimeout) as ei:
            tr._quiesce(timeout=0.3)
        msg = str(ei.value)
        assert "fleet0" in msg
        assert "claimed=" in msg and "watermark=" in msg
        assert "heartbeat_age=" in msg and "state=alive" in msg
        tr._resume_admission()
    finally:
        tr.close()


def test_quiesce_all_dead_raises_supervisor_error():
    plan = FaultPlan([FaultSpec(site="actor", exc=InjectedActorDeath)])
    tr = _FakeRollFleet(tiny_cfg(), fleet_cfg(fleet=1, max_staleness=2),
                        chaos=plan)
    try:
        tr._ensure_actor()
        _wait_for(lambda: tr.supervisor.all_dead(), msg="sole replica death")
        with pytest.raises(SupervisorError, match="dead or condemned") as ei:
            tr._quiesce(timeout=5.0)
        assert ei.value.statuses and ei.value.statuses[0].dead
    finally:
        tr.close()


# --------------------------------------------- property: random schedules
K_PROP = 4


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10**6), fleet=st.integers(2, 3))
def test_chaos_property_random_schedules(seed, fleet):
    """Any seeded FaultPlan over N replicas (kills, stalls, put-failures):
    the run either delivers a token-exact serial prefix of groups or
    raises a clean structured SupervisorError — never a deadlock, never a
    lost or double-consumed group."""
    replicas = [f"fleet{i}" for i in range(fleet)]
    plan = FaultPlan.random(seed, replicas=replicas, max_index=K_PROP,
                            max_faults=3, stall_delay=0.8)
    tr = _FakeRollFleet(
        tiny_cfg(), fleet_cfg(fleet=fleet, max_staleness=K_PROP,
                              hang_timeout=0.3),
        chaos=plan)
    got, err = {}, None
    try:
        oracle = {i: _fake_tokens(i, tr._key_chain.keys_for(i)[1])
                  for i in range(K_PROP)}
        tr._ensure_actor()
        try:
            while len(got) < K_PROP:
                # a timeout here IS the deadlock the supervision layer
                # promises cannot happen — fail loudly, not silently
                g = tr.queue.pop(0, timeout=30.0)
                assert g.index not in got, "group double-served"
                got[g.index] = g
        except SupervisorError as e:
            err = e
    finally:
        tr.close()
    # delivered groups form a gapless serial prefix, each token-exact
    assert sorted(got) == list(range(len(got)))
    for i, g in got.items():
        np.testing.assert_array_equal(g.batch.tokens, oracle[i])
    if err is not None:
        assert err.statuses, "SupervisorError must carry replica statuses"
        assert all(s.dead or s.condemned for s in err.statuses)
    else:
        assert len(got) == K_PROP


# ----------------------------------------- real engines (slow, CI chaos lane)
@pytest.mark.slow
def test_fleet2_kill_one_replica_token_exact_vs_oracle():
    """THE recovery gate: a fleet of 2 with fleet1 killed by an injected
    death produces the same per-group rollouts as the no-fault fleet of 2
    — recovery is invisible in the sample stream."""
    cfg, k = tiny_cfg(), 3

    def collect(chaos):
        tr = DistNATGRPOTrainer(
            cfg, fleet_cfg(fleet=2, max_staleness=k, hang_timeout=300.0),
            chaos=chaos)
        got = {}
        try:
            tr._ensure_actor()
            while len(got) < k:
                g = tr.queue.pop(0, timeout=120.0)
                got[g.index] = g
            stats = tr.publication_stats()
        finally:
            tr.close()
        return got, stats

    oracle, _ = collect(None)
    plan = FaultPlan([FaultSpec(site="actor", replica="fleet1",
                                exc=InjectedActorDeath)])
    got, stats = collect(plan)
    assert set(got) == set(oracle) == set(range(k))
    for i in range(k):
        np.testing.assert_array_equal(got[i].batch.tokens,
                                      oracle[i].batch.tokens)
        np.testing.assert_array_equal(got[i].batch.response_lens,
                                      oracle[i].batch.response_lens)
        np.testing.assert_array_equal(np.asarray(got[i].key_sel),
                                      np.asarray(oracle[i].key_sel))
        assert got[i].behavior_version == 0
    sup = stats["supervisor"]
    assert sup["replicas_failed"] == 1 and sup["groups_reclaimed"] == 1
    assert plan.exhausted()
    assert "fleet1" not in stats["watermarks"]


@pytest.mark.slow
def test_placement_retry_under_pool_pressure():
    """Transient PagePoolExhausted at engine drive is retried on a fresh
    per-group session (bounded) instead of killing the replica."""
    from repro.rl.engine import PagePoolExhausted

    plan = FaultPlan([FaultSpec(site="placement", exc=PagePoolExhausted,
                                times=2)])
    tr = DistNATGRPOTrainer(
        tiny_cfg(),
        fleet_cfg(fleet=1, max_staleness=1, rollout_engine="paged",
                  hang_timeout=300.0, placement_retries=3,
                  placement_backoff=0.01),
        chaos=plan)
    try:
        tr._ensure_actor()
        g = tr.queue.pop(0, timeout=180.0)
        assert g.index == 0
        stats = tr.publication_stats()
        assert stats["placement_retries"] == 2
        assert stats["supervisor"]["replicas_failed"] == 0
        assert plan.exhausted()
    finally:
        tr.close()
