"""The step's own instrumentation: host spans (``nat.*`` profiler
annotations) around each part of a trainer step and each engine round, the
engine's round counters, and the name scopes the jitted programs carry into
their op metadata (what a device trace attributes time by)."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import (
    AsyncNATGRPOTrainer,
    NATGRPOTrainer,
    NATTrainerConfig,
    RolloutConfig,
    VOCAB_SIZE,
)

STEP_SPANS = ("nat.train_step", "nat.rollout", "nat.select", "nat.layout",
              "nat.learn", "nat.learn.dispatch", "nat.learn.sync",
              "nat.publish")
ROUND_PARTS = ("nat.engine.sync", "nat.engine.harvest", "nat.engine.place",
               "nat.engine.dispatch")


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
        steps_per_sync=2, rollout_engine="paged", layout="packed",
        num_buckets=1,
        adamw=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50),
        bucket_align=8, seed=0)
    base.update(kw)
    return NATTrainerConfig(**base)


@pytest.fixture(scope="module")
def traced_step(tmp_path_factory):
    """One warm trainer step under the profiler: its metrics and the
    (start, end, name) of every ``nat.`` span on the host planes."""
    tr = NATGRPOTrainer(tiny_cfg(), trainer_cfg())
    tr.train_step()                       # compiles outside the trace
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        m = tr.train_step()
    tr.close()
    path = next(out.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = [(e.start_ns, e.end_ns, e.name) for plane in data.planes
             if plane.name.startswith("/host:") for line in plane.lines
             for e in line.events if e.name.startswith("nat.")]
    return m, spans


def test_step_records_every_span(traced_step):
    m, spans = traced_step
    names = collections.Counter(n for _, _, n in spans)
    for name in STEP_SPANS + ("nat.engine.round",) + ROUND_PARTS:
        assert names[name] >= 1, (name, names)
    assert names["nat.train_step"] == 1
    (lo, hi), = [(s, e) for s, e, n in spans if n == "nat.train_step"]
    assert all(lo <= s <= e <= hi for s, e, _ in spans)


def test_round_parts_nest_inside_their_round(traced_step):
    m, spans = traced_step
    rounds = sorted((s, e) for s, e, n in spans if n == "nat.engine.round")
    parts = [(s, e, n) for s, e, n in spans if n in ROUND_PARTS]
    per_round = collections.Counter()
    for s, e, n in parts:
        owner = [r for r in rounds if r[0] <= s and e <= r[1]]
        assert len(owner) == 1, (n, s, e)
        per_round[owner[0], n] += 1
    # every round syncs and harvests exactly once; the rounds that dispatch
    # a step are the ones the engine counts (the last drives only harvest)
    for r in rounds:
        assert per_round[r, "nat.engine.sync"] == 1
        assert per_round[r, "nat.engine.harvest"] == 1
        assert per_round[r, "nat.engine.dispatch"] <= 1
    dispatched = sum(per_round[r, "nat.engine.dispatch"] for r in rounds)
    assert dispatched == m["rollout_rounds"] > 0


def test_learn_children_nest_inside_learn(traced_step):
    _, spans = traced_step
    (lo, hi), = [(s, e) for s, e, n in spans if n == "nat.learn"]
    for name in ("nat.learn.dispatch", "nat.learn.sync", "nat.publish"):
        (s, e), = [(s, e) for s, e, n in spans if n == name]
        assert lo <= s <= e <= hi


@pytest.mark.parametrize("engine", ["paged", "continuous"])
def test_round_split_fits_in_rollout_time(engine):
    tr = NATGRPOTrainer(tiny_cfg(), trainer_cfg(rollout_engine=engine))
    ms = [tr.train_step() for _ in range(2)]
    tr.close()
    for m in ms:
        assert m["rollout_rounds"] > 0
        assert m["rollout_rounds"] * 2 == m["rollout_decode_steps"]
        assert m["rollout_sync_s"] > 0 and m["rollout_host_s"] > 0
        assert m["rollout_sync_s"] + m["rollout_host_s"] <= m["time_rollout"]
        assert "queue_depth" not in m and "learner_tokens" not in m


def test_streaming_groups_carry_round_counters():
    tr = AsyncNATGRPOTrainer(tiny_cfg(), trainer_cfg(max_staleness=1))
    try:
        ms = [tr.train_step() for _ in range(3)]
        total = dict(tr.engine.stats)
    finally:
        tr.close()
    # each group carries the session counters' deltas over its admission
    # to its assembly: the engine's rounds in that interval and their split
    for m in ms:
        assert 0 < m["rollout_rounds"] <= total["rounds"]
        assert m["rollout_sync_s"] > 0 and m["rollout_host_s"] > 0
        assert m["rollout_sync_s"] + m["rollout_host_s"] <= m["time_rollout"]


def scope_names(text: str) -> set:
    """Every component of the name paths in lowered text's locations."""
    return {part for loc in re.findall(r'loc\("([^"]*)"', text)
            for part in loc.split("/")}


def _engine_text(trainer):
    eng = trainer.engine
    params = trainer.params
    eng.begin(params, jax.random.PRNGKey(0))
    st = eng._state
    s_slots, tp = eng.ecfg.num_slots, eng.ecfg.max_prompt_len
    if hasattr(eng, "_max_pages"):
        lanes, gmax = eng.ecfg.group_lanes, eng.ecfg.max_group
        rw = eng.ecfg.resumes
        args = (params, st,
                np.zeros((s_slots, eng._max_pages), np.int32),
                np.zeros((eng.num_pages,), bool),
                np.zeros((lanes, tp), np.int32), np.ones((lanes,), np.int32),
                np.zeros((lanes,), np.int32),
                np.zeros((lanes, eng._n_pp), np.int32),
                np.zeros((lanes, eng._n_pp), np.int32),
                np.zeros((lanes, gmax), np.int32),
                np.zeros((lanes, gmax), np.int32), np.zeros((lanes,), bool),
                np.zeros((rw,), np.int32),
                np.zeros((rw, trainer.model_cfg.vocab_size), np.float32),
                np.ones((rw,), np.int32), np.zeros((rw,), np.int32),
                np.zeros((rw,), bool), np.zeros((s_slots,), bool))
    else:
        lanes = eng.ecfg.lanes
        args = (params, st, np.zeros((lanes, tp), np.int32),
                np.ones((lanes,), np.int32), np.zeros((lanes,), np.int32),
                np.zeros((lanes,), np.int32), np.zeros((lanes,), bool),
                np.zeros((s_slots,), bool))
    return eng._step.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("engine,name,scopes", [
    ("paged", "paged_engine_step",
     ("engine.invalidate", "engine.prefill", "engine.resume",
      "engine.decode", "engine.sample", "paged_decode_attn",
      "paged_decode_attn.write")),
    ("continuous", "engine_step",
     ("engine.invalidate", "engine.prefill", "engine.decode",
      "engine.sample")),
])
def test_engine_step_names_its_parts(engine, name, scopes):
    tr = NATGRPOTrainer(tiny_cfg(), trainer_cfg(rollout_engine=engine))
    text = _engine_text(tr)
    tr.close()
    assert f"module @jit_{name} " in text
    names = scope_names(text)
    for scope in scopes:
        assert scope in names, scope


def test_learner_step_names_its_parts():
    tr = NATGRPOTrainer(tiny_cfg(), trainer_cfg())
    rows, t = 2, 24
    batch = {k: jnp.zeros((rows, t), jnp.int32) for k in (
        "tokens", "positions", "segment_ids", "resp_ids")}
    b = 8
    batch.update({k: jnp.zeros((rows, t), jnp.float32) for k in (
        "response_mask", "old_logp", "ht_weights", "behavior_logp")})
    batch.update({k: jnp.zeros((b,), jnp.float32) for k in (
        "advantages", "orig_lengths", "staleness")})
    text = tr._train_step.lower(tr.params, tr.opt_state, batch).as_text(
        debug_info=True)
    tr.close()
    assert "module @jit_learner_step " in text
    names = scope_names(text)
    for scope in ("jvp(learner.loss)", "transpose(jvp(learner.loss))",
                  "learner.optimizer"):
        assert scope in names, scope
