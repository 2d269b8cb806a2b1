"""Paged KV arena: greedy parity with the legacy scan AND the dense slot
arena, group-level prompt-prefix sharing, page lifecycle (refcount drop on
retire/cancel -> free list), gather isolation, allocator exhaustion, and
the learner-batch contract on the paged path (DESIGN.md §8)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import init_params, model_decl
from repro.models.config import ModelConfig, dense_blocks
from repro.optim import AdamWConfig
from repro.rl import (
    ContinuousRolloutEngine,
    EngineConfig,
    NATGRPOTrainer,
    NATTrainerConfig,
    PageAllocator,
    PagedEngineConfig,
    PagedRolloutEngine,
    PagePoolExhausted,
    Request,
    RolloutConfig,
    VOCAB_SIZE,
)
from repro.rl.rollout import generate, rollout_group_continuous


def tiny_cfg():
    return ModelConfig(name="tiny", d_model=64, n_heads=4, n_kv_heads=2,
                       head_dim=16, d_ff=128, vocab_size=VOCAB_SIZE,
                       blocks=dense_blocks(2), seq_parallel=False,
                       remat_policy="none", scan_layers=False)


@pytest.fixture(scope="module")
def setup():
    cfg = tiny_cfg()
    key = jax.random.PRNGKey(0)
    params = init_params(key, model_decl(cfg))
    rng = np.random.default_rng(0)
    prompts = rng.integers(3, VOCAB_SIZE, size=(5, 10)).astype(np.int32)
    plens = np.full((5,), 10, np.int32)
    return cfg, params, prompts, plens, key


# ------------------------------------------------------------ allocator unit
def test_page_allocator_refcounts_and_free_list():
    a = PageAllocator(6)
    p1 = a.alloc(2)
    assert a.in_use == 2 and a.num_free == 4
    a.retain(p1)           # second sibling holds the prompt pages
    a.retain(p1)           # third
    assert a.release(p1) == []          # 2 refs left: nothing freed
    assert a.release(p1) == []          # 1 ref left
    assert sorted(a.release(p1)) == sorted(p1)  # last ref: back to free list
    assert a.in_use == 0 and a.num_free == 6
    d = a.alloc(1)
    assert a.release(d) == d            # refcount-1 decode page frees at once
    assert a.peak_in_use == 2           # max concurrent in_use ever observed


def test_page_allocator_exhaustion_raises():
    a = PageAllocator(2)
    a.alloc(2)
    with pytest.raises(PagePoolExhausted, match="2/2 pages in use"):
        a.alloc(1)


# ------------------------------------------------------------- greedy parity
def test_greedy_parity_with_legacy_and_dense(setup):
    """Acceptance gate: the paged engine reproduces legacy dense-arena
    completions token-exactly under greedy decoding, with recycling (fewer
    slots than requests) and a partial last prompt page (10 % 4 != 0)."""
    cfg, params, prompts, plens, key = setup
    n = 8
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    full, logps, ents, _, _ = generate(
        params, cfg, rcfg, jnp.asarray(prompts), jnp.asarray(plens), key)
    full, logps, ents = map(np.asarray, (full, logps, ents))

    dense = ContinuousRolloutEngine(cfg, rcfg, EngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=3, refill_lanes=1))
    paged = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=3, page_len=4,
        max_group=2))
    reqs = [Request(uid=i, tokens=prompts[i], budget=n) for i in range(5)]
    comps_d = {c.uid: c for c in dense.run(params, reqs, key)}
    comps_p = {c.uid: c for c in paged.run(params, reqs, key)}
    assert len(comps_p) == 5
    tp = prompts.shape[1]
    for i in range(5):
        c = comps_p[i]
        rl = c.response_len
        np.testing.assert_array_equal(c.tokens, full[i, tp:tp + rl])
        np.testing.assert_allclose(c.logp, logps[i, :rl], atol=1e-5)
        np.testing.assert_allclose(c.entropy, ents[i, :rl], atol=1e-5)
        np.testing.assert_array_equal(c.tokens, comps_d[i].tokens)
    # every page returned to the free list once the session drained
    assert paged._alloc.in_use == 0


def test_group_prefix_sharing_prefills_once(setup):
    """One prompt prefill per group; under greedy every sibling reproduces
    the legacy completion; prompt pages are shared (peak pages well under
    the dense-equivalent private-prompt budget)."""
    cfg, params, prompts, plens, key = setup
    n, g = 8, 4
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    full, logps, _, _, _ = generate(
        params, cfg, rcfg, jnp.asarray(prompts[:2]),
        jnp.asarray(plens[:2]), key)
    full, logps = np.asarray(full), np.asarray(logps)

    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2 * g, max_prompt_len=10, steps_per_sync=2, page_len=4,
        max_group=g, group_lanes=2))
    eng.begin(params, key)
    for pi in range(2):
        eng.submit_group([Request(uid=pi * g + j, tokens=prompts[pi],
                                  budget=n) for j in range(g)])
    comps = {c.uid: c for c in eng.drain()}
    assert len(comps) == 2 * g
    tp = prompts.shape[1]
    for pi in range(2):
        for j in range(g):
            c = comps[pi * g + j]
            np.testing.assert_array_equal(
                c.tokens, full[pi, tp:tp + c.response_len])
            np.testing.assert_allclose(c.logp, logps[pi, :c.response_len],
                                       atol=1e-5)
    st = eng.stats
    assert st["prompt_prefills"] == 2          # one prefill per group
    # prompt pages per group: ceil(10/4) = 3, counted ONCE per group;
    # decode pages: ceil(8/4) = 2 per sibling
    assert st["peak_pages_in_use"] <= 2 * (3 + g * 2)
    # dense-equivalent (private prompts) would hold 2 * g * (3 + 2) pages
    assert st["peak_pages_in_use"] < 2 * g * (3 + 2)


def test_parked_siblings_resume_without_reprefill(setup):
    """A group wider than the arena: siblings beyond the free slots park
    and later RESUME into freed slots from the shared prompt pages + saved
    prompt logits — still exactly one prefill, still legacy-exact greedy
    completions (group width never serializes the arena)."""
    cfg, params, prompts, plens, key = setup
    n, g = 8, 4
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    full, logps, _, _, _ = generate(
        params, cfg, rcfg, jnp.asarray(prompts[:1]), jnp.asarray(plens[:1]),
        key)
    full, logps = np.asarray(full), np.asarray(logps)

    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=3, page_len=4,
        max_group=g))
    eng.begin(params, key)
    eng.submit_group([Request(uid=j, tokens=prompts[0], budget=n)
                      for j in range(g)])
    assert not eng.idle
    comps = {c.uid: c for c in eng.drain()}
    assert len(comps) == g and eng.idle
    tp = prompts.shape[1]
    for j in range(g):
        c = comps[j]
        np.testing.assert_array_equal(c.tokens, full[0, tp:tp + c.response_len])
        np.testing.assert_allclose(c.logp, logps[0, :c.response_len],
                                   atol=1e-5)
    assert eng.stats["prompt_prefills"] == 1  # parked siblings never re-prefill
    assert eng._alloc.in_use == 0


def test_stateful_mixer_places_atomically(setup):
    """Per-slot-state mixers (local rings here) run the paged arena with
    atomic group placement — non-attention states broadcast to sibling
    slots on device — and reproduce the legacy scan under greedy; the
    default num_slots in rollout_group_continuous covers one G' group."""
    _, _, prompts, plens, key = setup
    local_cfg = ModelConfig(name="tiny-local", d_model=64, n_heads=4,
                            n_kv_heads=2, head_dim=16, d_ff=128,
                            vocab_size=VOCAB_SIZE, window=8,
                            blocks=dense_blocks(2, mixer="local"),
                            seq_parallel=False, remat_policy="none",
                            scan_layers=False)
    params = init_params(jax.random.PRNGKey(1), model_decl(local_cfg))
    n, g = 6, 2
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    full, logps, _, _, _ = generate(
        params, local_cfg, rcfg, jnp.asarray(prompts[:2]),
        jnp.asarray(plens[:2]), key)
    full, logps = np.asarray(full), np.asarray(logps)

    eng = PagedRolloutEngine(local_cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=2, page_len=4,
        max_group=g))
    assert not eng._pure_pool
    groups = [[Request(uid=pi * g + j, tokens=prompts[pi], budget=n)
               for j in range(g)] for pi in range(2)]
    comps = {c.uid: c for c in eng.run_groups(params, groups, key)}
    assert len(comps) == 2 * g
    tp = prompts.shape[1]
    for pi in range(2):
        for j in range(g):
            c = comps[pi * g + j]
            np.testing.assert_array_equal(
                c.tokens, full[pi, tp:tp + c.response_len])
            np.testing.assert_allclose(c.logp, logps[pi, :c.response_len],
                                       atol=1e-5)
    # overprovisioned default sizing must not under-provision max_group
    rcfg2 = RolloutConfig(max_new_tokens=4, group_size=2, overprovision=1.5)
    rb = rollout_group_continuous(params, local_cfg, rcfg2, prompts[:1],
                                  plens[:1], key, steps_per_sync=2,
                                  paged=True, page_len=4)
    assert rb.tokens.shape[0] == 2  # G kept rows from a G'=3 group


# ------------------------------------------------------------ page lifecycle
def test_retire_returns_pages_and_recycles(setup):
    """Refcount drop on retirement returns pages to the free list, and a
    recycled page serves a later request without leaking its previous
    occupant (the arena is sized so reuse is forced)."""
    cfg, params, prompts, plens, key = setup
    n = 8
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    full, _, _, _, _ = generate(
        params, cfg, rcfg, jnp.asarray(prompts), jnp.asarray(plens), key)
    full = np.asarray(full)
    # 5 sequential requests, pool sized for ~one request: ceil(10/4) +
    # ceil(8/4) = 5 pages needed per request; give it 6 so every
    # placement must recycle freed pages
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=1, max_prompt_len=10, steps_per_sync=4, page_len=4,
        num_pages=6, max_group=1))
    reqs = [Request(uid=i, tokens=prompts[i], budget=n) for i in range(5)]
    comps = {c.uid: c for c in eng.run(params, reqs, key)}
    tp = prompts.shape[1]
    for i in range(5):
        np.testing.assert_array_equal(
            comps[i].tokens, full[i, tp:tp + comps[i].response_len])
    assert eng._alloc.in_use == 0
    assert eng._alloc.peak_in_use <= 6


def test_cancel_frees_pages_immediately(setup):
    """APRIL cancellation: the straggler's pages return to the free list in
    the same round the host learns of the cancellation."""
    cfg, params, prompts, plens, key = setup
    rcfg = RolloutConfig(max_new_tokens=32, temperature=1.0, eos_id=-1)
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=2, page_len=4,
        max_group=1))
    reqs = [Request(uid=0, tokens=prompts[0], budget=2),
            Request(uid=1, tokens=prompts[1], budget=32),
            Request(uid=2, tokens=prompts[2], budget=32)]

    def on_finish(c):
        return [1, 2] if c.uid == 0 else None

    comps = {c.uid: c for c in eng.run(params, reqs, key, on_finish=on_finish)}
    assert comps[1].cancelled and comps[1].response_len < 32
    assert comps[2].cancelled and comps[2].response_len == 0  # never placed
    assert eng.stats["cancelled"] == 2
    assert eng.stats["decode_steps"] < 32
    assert eng._alloc.in_use == 0  # cancellation released everything


def test_deferred_group_cancellation_emits_once(setup):
    """A cancelled sibling of a group stuck at the queue head (waiting on
    pages/slots) must emit exactly ONE Completion, however many rounds the
    group waits before placing."""
    cfg, params, prompts, plens, key = setup
    rcfg = RolloutConfig(max_new_tokens=8, temperature=1.0, eos_id=-1)
    # pool sized so group B cannot place while group A decodes: A needs
    # 3 prompt + up to 2 decode pages of the 7-page pool, leaving < the
    # 3 + 1 pages B's placement needs
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=2, page_len=4,
        num_pages=7, max_group=1))
    eng.begin(params, key)
    eng.submit_group([Request(uid=0, tokens=prompts[0], budget=8)])
    eng.submit_group([Request(uid=1, tokens=prompts[1], budget=8)])
    eng.drive()               # A places; B waits on pages
    eng.cancel([1])
    comps = eng.drain()
    assert sorted(c.uid for c in comps) == [0, 1]  # exactly one each
    by_uid = {c.uid: c for c in comps}
    assert by_uid[1].cancelled and by_uid[1].response_len == 0
    assert eng.stats["cancelled"] == 1


def test_gather_isolation_across_groups(setup):
    """No slot can read another group's decode pages: per-slot decode pages
    are disjoint, prompt pages are shared only within a group, and zeroing
    every page OUTSIDE one slot's block table leaves its next-token logits
    untouched."""
    cfg, params, prompts, plens, key = setup
    n = 8
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=4, max_prompt_len=10, steps_per_sync=2, page_len=4,
        max_group=2, group_lanes=2))
    eng.begin(params, key)
    eng.submit_group([Request(uid=j, tokens=prompts[0], budget=n)
                      for j in range(2)])
    eng.submit_group([Request(uid=2 + j, tokens=prompts[1], budget=n)
                      for j in range(2)])
    eng.drive()
    eng.drive()
    # host invariants: decode pages pairwise disjoint; prompt pages shared
    # within a group, disjoint across groups
    dec = [set(eng._slot_decode_pages[s]) for s in range(4)]
    for a in range(4):
        for b in range(a + 1, 4):
            assert not dec[a] & dec[b], (a, b)
    pp = [tuple(eng._slot_prompt_pages[s]) for s in range(4)]
    assert pp[0] == pp[1] and pp[2] == pp[3] and set(pp[0]).isdisjoint(pp[2])

    # device invariant: pages outside slot 0's table are invisible to it
    state = eng._state
    bt = np.full((4, eng._max_pages), -1, np.int32)
    for s in range(4):
        n_pp_s = -(-int(eng._slot_plen[s]) // 4)
        bt[s, :n_pp_s] = eng._slot_prompt_pages[s]
        dp = eng._slot_decode_pages[s]
        bt[s, n_pp_s:n_pp_s + len(dp)] = dp
    owned = {p for p in bt[0] if p >= 0}

    def poison(leaf):
        if leaf.ndim >= 3 and leaf.shape[1] == eng.num_pages:
            mask = np.ones((eng.num_pages,), bool)
            mask[sorted(owned)] = False
            shape = (1, eng.num_pages) + (1,) * (leaf.ndim - 2)
            return jnp.where(jnp.asarray(mask).reshape(shape), 0, leaf)
        return leaf

    from repro.models.model import decode_step
    poisoned = jax.tree.map(poison, state["cache"])
    tok = jnp.argmax(state["logits"], axis=-1).astype(jnp.int32)
    wp = jnp.full((4,), eng.num_pages, jnp.int32)  # read-only probe
    wo = jnp.zeros((4,), jnp.int32)
    logits_a, _ = decode_step(params, cfg, tok, state["cache"], state["pos"],
                              block_tables=jnp.asarray(bt), write_page=wp,
                              write_off=wo)
    logits_b, _ = decode_step(params, cfg, tok, poisoned, state["pos"],
                              block_tables=jnp.asarray(bt), write_page=wp,
                              write_off=wo)
    np.testing.assert_array_equal(np.asarray(logits_a)[0],
                                  np.asarray(logits_b)[0])


def test_allocator_exhaustion_surfaces_clearly(setup):
    """An undersized pool raises PagePoolExhausted (with occupancy in the
    message) instead of silently corrupting the arena: two long-budget
    slots outgrow a pool sized for their placement but not their decode."""
    cfg, params, prompts, plens, key = setup
    rcfg = RolloutConfig(max_new_tokens=32, temperature=1.0, eos_id=-1)
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=4, page_len=4,
        num_pages=11, max_group=1))
    # each slot: 3 prompt pages + up to ceil(32/4)=8 decode pages; two
    # slots can place (3+1 + 3+1 = 8 <= 11) but cannot both run to budget
    reqs = [Request(uid=i, tokens=prompts[i], budget=32) for i in range(2)]
    with pytest.raises(PagePoolExhausted, match="pages in use"):
        eng.run(params, reqs, key)
    # a group that can NEVER fit is rejected at submit time
    eng2 = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=2, max_prompt_len=10, steps_per_sync=4, page_len=4,
        num_pages=8, max_group=2))
    eng2.begin(params, key)
    with pytest.raises(PagePoolExhausted, match="grow PagedEngineConfig"):
        eng2.submit_group([Request(uid=i, tokens=prompts[0], budget=32)
                           for i in range(2)])


def test_submit_group_validates_siblings(setup):
    cfg, params, prompts, plens, key = setup
    rcfg = RolloutConfig(max_new_tokens=8, temperature=1.0, eos_id=-1)
    eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
        num_slots=4, max_prompt_len=10, page_len=4, max_group=2))
    eng.begin(params, key)
    with pytest.raises(ValueError, match="share one prompt"):
        eng.submit_group([Request(uid=0, tokens=prompts[0]),
                          Request(uid=1, tokens=prompts[1])])
    with pytest.raises(ValueError, match="max_group"):
        eng.submit_group([Request(uid=i, tokens=prompts[0])
                          for i in range(3)])
    # per-slot-state mixers (here: local rings) cannot park siblings, so
    # their groups must fit the arena atomically
    local_cfg = ModelConfig(name="tiny-local", d_model=64, n_heads=4,
                            n_kv_heads=2, head_dim=16, d_ff=128,
                            vocab_size=VOCAB_SIZE, window=8,
                            blocks=dense_blocks(2, mixer="local"),
                            seq_parallel=False, remat_policy="none",
                            scan_layers=False)
    with pytest.raises(ValueError, match="max_group cannot exceed"):
        PagedRolloutEngine(local_cfg, rcfg, PagedEngineConfig(
            num_slots=2, max_prompt_len=10, max_group=4))


def test_kernel_impl_matches_ref(setup):
    """attn_impl='kernel' (Pallas block-table gather) reproduces the jnp
    gather path: greedy tokens exact; logp within the cross-structure
    reassociation tolerance (cf. the teacher-forced parity note)."""
    cfg, params, prompts, plens, key = setup
    n = 6
    rcfg = RolloutConfig(max_new_tokens=n, temperature=0.0, eos_id=-1)
    outs = {}
    for impl in ("ref", "kernel"):
        eng = PagedRolloutEngine(cfg, rcfg, PagedEngineConfig(
            num_slots=2, max_prompt_len=10, steps_per_sync=2, page_len=5,
            max_group=2, attn_impl=impl))
        eng.begin(params, key)
        eng.submit_group([Request(uid=j, tokens=prompts[0], budget=n)
                          for j in range(2)])
        outs[impl] = {c.uid: c for c in eng.drain()}
    for uid, c in outs["ref"].items():
        np.testing.assert_array_equal(c.tokens, outs["kernel"][uid].tokens)
        np.testing.assert_allclose(c.logp, outs["kernel"][uid].logp,
                                   atol=2e-2)


# --------------------------------------------------------- learner contract
def test_rollout_group_continuous_paged_contract(setup):
    """rollout_group_continuous(paged=True) produces the same learner-batch
    contract as the dense path, with group prefills counted."""
    cfg, params, prompts, plens, key = setup
    rcfg = RolloutConfig(max_new_tokens=8, group_size=4, overprovision=1.5)
    rb = rollout_group_continuous(params, cfg, rcfg, prompts[:3], plens[:3],
                                  key, num_slots=6, steps_per_sync=2,
                                  paged=True, page_len=4)
    b = 3 * 4
    assert rb.tokens.shape == (b, 10 + 8)
    for i in range(b):
        pl, rl = int(rb.prompt_lens[i]), int(rb.response_lens[i])
        row = rb.response_mask[i]
        assert row[:pl].sum() == 0
        assert row[pl:pl + rl].sum() == rl
        assert np.all(rb.old_logp[i][row == 0] == 0)
    st = rb.stats
    assert st["tokens_budget"] == 3 * 6 * 8
    assert 0 < st["tokens_generated"] <= st["tokens_budget"]
    assert st["prompt_prefills"] == 3  # one per prompt, not per sibling


def test_trainer_paged_rollout_metrics():
    """End-to-end: NATGRPOTrainer on rollout_engine='paged' trains and
    surfaces the rollout token accounting."""
    cfg = tiny_cfg()
    tc = NATTrainerConfig(
        selector="rpc", selector_kwargs=(("min_cut", 4),),
        prompts_per_step=2, max_prompt_len=16,
        rollout=RolloutConfig(max_new_tokens=8, group_size=4,
                              overprovision=1.5),
        rollout_engine="paged", page_len=8, steps_per_sync=2,
        adamw=AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20),
        bucket_align=8, seed=0)
    tr = NATGRPOTrainer(cfg, tc)
    m = tr.train_step()
    assert np.isfinite(m["loss"])
    assert m["tokens_budget"] == 2 * 6 * 8
    assert 0 < m["tokens_generated"] <= m["tokens_budget"]


# ------------------------------------------- allocator property tests


def _check_partition(a):
    """The free list and the live refcounts partition the pool exactly:
    every page is either on the free list (refcount 0) or live
    (refcount > 0), never both, never neither, never twice."""
    free = a._free
    assert len(free) == len(set(free)), "free list holds a page twice"
    live = set(np.flatnonzero(a.refcount > 0).tolist())
    assert live.isdisjoint(free), "page simultaneously free and live"
    assert len(live) + len(free) == a.num_pages, "pages leaked"
    assert a.in_use == len(live)
    assert np.all(a.refcount >= 0)


@settings(max_examples=60)
@given(st.integers(min_value=2, max_value=12),
       st.lists(st.integers(min_value=0, max_value=10 ** 6),
                min_size=1, max_size=80))
def test_page_allocator_interleavings_never_double_free_or_leak(
        num_pages, ops):
    """Arbitrary alloc/retain/release interleavings keep the free list +
    refcounts an exact partition of the pool (the invariant that makes
    retire a free-list push and cancellation safe mid-group)."""
    a = PageAllocator(num_pages)
    handles = []          # (pages, model_refs) for every live allocation
    for op in ops:
        kind = op % 3
        if kind == 0:                       # alloc 1..3 pages
            n = 1 + (op // 3) % 3
            if n > a.num_free:
                with pytest.raises(PagePoolExhausted):
                    a.alloc(n)
            else:
                pages = a.alloc(n)
                assert len(pages) == n
                assert all(a.refcount[p] == 1 for p in pages)
                handles.append([pages, 1])
        elif kind == 1 and handles:         # retain (another sibling)
            h = handles[(op // 3) % len(handles)]
            a.retain(h[0])
            h[1] += 1
        elif kind == 2 and handles:         # release one reference
            i = (op // 3) % len(handles)
            h = handles[i]
            freed = a.release(h[0])
            h[1] -= 1
            # pages free exactly when the LAST reference drops
            if h[1] == 0:
                assert sorted(freed) == sorted(h[0])
                handles.pop(i)
            else:
                assert freed == []
        _check_partition(a)
    # drain: dropping every remaining reference returns the whole pool
    for pages, refs in handles:
        for _ in range(refs):
            a.release(pages)
    _check_partition(a)
    assert a.in_use == 0 and a.num_free == num_pages
    assert np.all(a.refcount == 0)


@settings(max_examples=40)
@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=0, max_value=8))
def test_page_allocator_exhaustion_reports_exact_occupancy(pool, held):
    """PagePoolExhausted names the exact in-use/free occupancy at the
    moment of failure — the numbers operators size num_pages from."""
    held = min(held, pool)
    a = PageAllocator(pool)
    if held:
        a.alloc(held)
    want = a.num_free + 1               # always one more than is free
    with pytest.raises(
            PagePoolExhausted,
            match=rf"allocating {want} page\(s\): {held}/{pool} pages "
                  rf"in use \({pool - held} free\)"):
        a.alloc(want)
    # a failed alloc must not perturb the pool
    _check_partition(a)
    assert a.in_use == held
