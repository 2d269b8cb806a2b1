"""The harness's run on the CPU, at a small size: its step loop, its
metrics, and that ``correct`` comes out false when the timed path is broken
underneath it, or when the control (the reference in float8) takes the
program's place.  The command itself refuses a CPU; these tests call the
harness's functions directly, with the cell's own files and limits, and
the model (two layers of width 1024, heads of 128) and the lengths cut
down."""
import dataclasses
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness as H

CELL = "qwen3_8b.cot_rpc"
SEED = 2**31 + 12345             # seeds may exceed 32 signed bits


def small_spec():
    spec = H.load_spec(CELL)
    spec["config"].update(hidden_size=1024, intermediate_size=3072,
                          num_attention_heads=8, num_key_value_heads=2,
                          head_dim=128, num_hidden_layers=2, vocab_size=2048)
    t = spec["traffic"]
    t["trainer"]["max_new_tokens"] = 48
    t["lengths"].update(median=24, min=4, max=48)
    t["warm_rows"] = [18, 32]
    return spec


def run(trace=False):
    return H.run(CELL, SEED, 1.0, trace, t_start=time.perf_counter(),
                 spec=small_spec(), peak_flops=1e12)


def test_sound_run_is_correct_and_compiles_nothing_in_the_window():
    out = run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"rl_tokens_per_s", "peak_hbm_gb",
                                   "setup_s"}
    assert out["metrics"]["rl_tokens_per_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_per_layer_readers(monkeypatch):
    spec = small_spec()
    recs = {}
    orig = H.RunRecord

    def keep(**kw):
        recs["run"] = orig(**kw)
        return recs["run"]

    monkeypatch.setattr(H, "RunRecord", keep)
    out = H.run(CELL, SEED, 1.0, False, t_start=time.perf_counter(),
                spec=spec, peak_flops=1e12)
    assert out["correct"]
    rec = recs["run"]
    got = {m["name"]: H._load_reader(m["name"], H.ROOT)(rec)
           for m in spec["per_layer"]}
    assert got["compiles_in_window"] == 0
    assert got["device_idle_share"] is None          # no trace on the CPU
    for name in ("rollout_s_per_step", "rollout_ms_per_substep",
                 "select_s_per_step", "learn_s_per_step", "learner_mfu",
                 "step_mfu"):
        assert got[name] > 0, name
    assert 0 < got["pack_efficiency"] <= 1


def _state_unchanged(monkeypatch):
    import repro.rl.async_trainer as AT
    make = AT.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)
        return lambda params, opt, batch: (params, opt,
                                           step(params, opt, batch)[2])

    monkeypatch.setattr(AT, "make_train_step", broken)


def _half_batch(monkeypatch):
    import repro.rl.learner as L
    loss = L.nat_grpo_loss

    def broken(logp, old_logp, adv, w, lens, cfg, **kw):
        half = kw["num_segments"] // 2
        w = w * (kw["segment_ids"] < half)
        value, metrics = loss(logp, old_logp, adv, w, lens, cfg, **kw)
        metrics = dict(metrics, loss=2 * value)
        return 2 * value, metrics

    monkeypatch.setattr(L, "nat_grpo_loss", broken)


def _token_altered(monkeypatch):
    from repro.rl.engine import PagedRolloutEngine
    harvest = PagedRolloutEngine._harvest
    done = set()

    def broken(self, s, host, cancelled):
        c = harvest(self, s, host, cancelled)
        if id(self) not in done and c.response_len:
            done.add(id(self))
            c.tokens = np.array(c.tokens)
            c.tokens[0] = (c.tokens[0] + 1) % self.cfg.vocab_size
        return c

    monkeypatch.setattr(PagedRolloutEngine, "_harvest", broken)


def _cut_early(monkeypatch):
    import repro.core.selectors as S
    call = S.RPCSelector.__call__

    def broken(self, key, response_mask):
        sel = call(self, key, response_mask)
        pos, length = S.response_positions(response_mask)
        lo = jnp.minimum(self.min_cut, length)
        cut = lo + (sel.keep_len - lo) // 2
        mask = (pos < cut[:, None]).astype(jnp.float32) * response_mask
        return dataclasses.replace(sel, mask=mask, keep_len=cut)

    monkeypatch.setattr(S.RPCSelector, "__call__", broken)


def _gradient_sign_flipped(monkeypatch):
    import jax

    import repro.rl.learner as L
    update = L.adamw_update

    def broken(params, grads, opt_state, cfg):
        return update(params, jax.tree.map(lambda g: -g, grads), opt_state,
                      cfg)

    monkeypatch.setattr(L, "adamw_update", broken)


def _wrong_temperature(monkeypatch):
    config = H.trainer_config

    def broken(traffic, seed32):
        tc = config(traffic, seed32)
        return dataclasses.replace(tc, rollout=dataclasses.replace(
            tc.rollout, temperature=tc.rollout.temperature
            * H.FAULT_TEMPERATURE))

    monkeypatch.setattr(H, "trainer_config", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _cut_early,
                                   _gradient_sign_flipped,
                                   _wrong_temperature])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run()
    assert not out["correct"], out["checks"]


def test_control_and_planted_faults_fail_the_limits():
    from bench import calibrate

    spec = small_spec()
    recs = []
    calibrate.calibrate(spec, CELL, [SEED], {SEED}, recs.append)
    got = {r["kind"]: r for r in recs}
    limits = spec["limits"]

    def fails(rec):
        return [k for k in limits if k in rec and rec[k] > limits[k]]

    assert not fails(got["program"]), got["program"]
    assert fails(got["control"]), got["control"]
    assert "loss_gap" in fails(got["half_batch"]), got["half_batch"]
    assert fails(got["altered_token"]) == ["logp_gap"]
    assert fails(got["wrong_temperature"]) == ["sample_z"]
    assert fails(got["cut_early"]) == ["cut_z"]
    assert "select_faults" in fails(got["not_prefix"]), got["not_prefix"]
