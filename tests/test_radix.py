"""Radix prefix cache: trie semantics (ready-next-round, longest match,
page-aligned insert, epoch flush) and the ownership protocol under random
insert/match/evict/flush interleavings — never double-free, never leak:
the allocator free list, live handles, and trie residents partition the
pool, and eviction never reclaims a page a live reader still names
(DESIGN.md §10)."""
import numpy as np
import pytest

from repro.rl import PageAllocator, RadixPrefixCache

from hypothesis import given, settings, strategies as st

PL = 4  # page_len for every trie in this file


def make(num_pages=32):
    a = PageAllocator(num_pages)
    return a, RadixPrefixCache(a, PL)


def toks(*chunks):
    """Concatenate per-page chunks given as ints: toks(1, 2) -> the 8-token
    prompt [1]*4 + [2]*4 (distinct chunk per int keeps keys readable)."""
    return np.concatenate([np.full((PL,), c, np.int32) for c in chunks])


def prefill_insert(a, cache, tokens, parent=None, start=0):
    """Engine-side insert: alloc fresh pages for the uncached full chunks
    (caller = the group holds ref 1), chain them into the trie (trie takes
    its own ref).  Returns (pages, nodes)."""
    n = (len(tokens) - start) // PL
    pages = a.alloc(n)
    nodes = cache.insert(parent, tokens, start, pages)
    return pages, nodes


# ------------------------------------------------------------- trie basics
def test_lookup_empty_and_partial_pages():
    _, cache = make()
    assert cache.lookup(toks(1, 2)) == []
    assert cache.lookup(np.int32([1, 2])) == []  # shorter than one page


def test_nodes_ready_only_after_step():
    """Pages inserted this round are still being written by this round's
    prefill dispatch — same-round lookups must not match them."""
    a, cache = make()
    prefill_insert(a, cache, toks(1, 2))
    assert cache.lookup(toks(1, 2)) == []          # same round: not ready
    cache.step()
    assert [n.page for n in cache.lookup(toks(1, 2))] == [0, 1]


def test_longest_match_is_chunkwise_and_prefix_only():
    a, cache = make()
    prefill_insert(a, cache, toks(1, 2, 3))
    cache.step()
    assert len(cache.lookup(toks(1, 2, 3))) == 3
    assert len(cache.lookup(toks(1, 2, 9))) == 2   # diverges at chunk 3
    assert len(cache.lookup(toks(9, 2, 3))) == 0   # diverges at chunk 1
    # a trailing partial page never extends the match
    assert len(cache.lookup(np.concatenate([toks(1, 2), [3, 3]]))) == 2


def test_insert_keeps_incumbent_and_branches():
    """Re-inserting a cached chunk keeps the incumbent node (the duplicate
    page stays caller-owned); new suffixes branch below the shared chain."""
    a, cache = make()
    p1, _ = prefill_insert(a, cache, toks(1, 2))
    cache.step()
    # second group with the same first chunk, diverging second chunk
    dup = a.alloc(2)
    nodes = cache.insert(None, toks(1, 9), 0, dup)
    assert len(nodes) == 1 and nodes[0].page == dup[1]
    # incumbent kept: dup[0] was NOT adopted, trie still points at p1[0]
    cache.step()
    assert [n.page for n in cache.lookup(toks(1, 2))] == p1
    assert [n.page for n in cache.lookup(toks(1, 9))] == [p1[0], dup[1]]
    # the un-adopted duplicate page carries only its caller reference
    assert int(a.refcount[dup[0]]) == 1


def test_insert_start_must_be_page_aligned():
    a, cache = make()
    with pytest.raises(AssertionError):
        cache.insert(None, toks(1, 2), 2, a.alloc(1))


# --------------------------------------------------------------- eviction
def test_evict_lru_leaves_first_and_cascades():
    a, cache = make()
    pA, _ = prefill_insert(a, cache, toks(1, 2))
    pB, _ = prefill_insert(a, cache, toks(5))
    cache.step()
    a.release(pA), a.release(pB)        # groups retire; trie refs remain
    cache.touch(cache.lookup(toks(1, 2)))   # A is now hotter than B
    freed = cache.evict(1)
    assert freed == [pB[0]]             # coldest leaf goes first
    # cascading: evicting 2 more frees A's leaf then its parent
    assert sorted(cache.evict(2)) == sorted(pA)
    assert cache.num_resident == 0
    assert a.in_use == 0


def test_evict_never_touches_pages_with_live_readers():
    a, cache = make()
    pages, _ = prefill_insert(a, cache, toks(1, 2))
    cache.step()
    # a second group matches the chain and retains it (engine commit path)
    a.retain(pages)
    a.release(pages)                    # first group retires
    assert cache.evict(8) == []         # reader still holds both pages
    a.release(pages)                    # reader retires
    assert sorted(cache.evict(8)) == sorted(pages)


def test_flush_starts_epoch_and_reaps_stragglers():
    a, cache = make()
    pA, _ = prefill_insert(a, cache, toks(1, 2))
    cache.step()
    a.release([pA[1]])                  # leaf is trie-only; root still read
    freed = cache.flush()
    assert freed == [pA[1]]             # evictable stale branch freed now
    assert cache.lookup(toks(1, 2)) == []   # stale epoch never matches
    # a fresh insert of the same tokens shadows the stale incumbent
    pB, _ = prefill_insert(a, cache, toks(1))
    cache.step()
    assert [n.page for n in cache.lookup(toks(1))] == pB
    a.release([pA[0]])                  # the straggler's reader drains
    assert cache.reap() == [pA[0]]
    assert cache.reap() == []           # stale fully drained -> cheap no-op
    assert cache.num_resident == 1


# ------------------------------------------------- property: ownership law
def _check_cache_partition(a, cache, live_handles):
    """Free list, live pages, and trie residents obey the ownership law:
    free/live partition the pool exactly, every trie resident is live, and
    every live page is reachable from a handle and/or the trie with the
    right multiplicity (trie holds exactly one ref per resident page)."""
    free = a._free
    assert len(free) == len(set(free)), "free list holds a page twice"
    live = set(np.flatnonzero(a.refcount > 0).tolist())
    assert live.isdisjoint(free), "page simultaneously free and live"
    assert len(live) + len(free) == a.num_pages, "pages leaked"
    resident = cache.resident_pages
    assert resident <= live, "trie names a freed page"
    expected = np.zeros((a.num_pages,), np.int32)
    for pages in live_handles:
        for p in pages:
            expected[p] += 1
    for p in resident:
        expected[p] += 1
    assert np.array_equal(expected, a.refcount), (
        "refcounts drifted from handles + trie residency")


@settings(max_examples=25)
@given(st.integers(min_value=6, max_value=24),
       st.lists(st.integers(min_value=0, max_value=9),
                min_size=20, max_size=60),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_radix_random_interleavings_never_leak_or_double_free(
        num_pages, script, seed):
    """Random insert/match-retain/retire/evict/flush/step interleavings:
    after every op the pool partitions exactly (no leak, no double-free)
    and eviction never frees a page a live group still reads.  At the end,
    retiring every group and evicting everything returns the whole pool."""
    rng = np.random.default_rng(seed)
    a = PageAllocator(num_pages)
    cache = RadixPrefixCache(a, PL)
    handles = []   # live groups: lists of pages each holds one ref on

    def new_prompt():
        n = int(rng.integers(1, 4))
        return np.asarray(rng.integers(0, 3, size=n * PL), np.int32)

    for op in script:
        if op <= 4:                       # place a group (engine commit)
            t = new_prompt()
            nodes = cache.lookup(t)
            m_pages = [nd.page for nd in nodes]
            n_fresh = len(t) // PL - len(m_pages)
            if n_fresh > a.num_free:
                cache.evict(n_fresh - a.num_free)
            if n_fresh > a.num_free:
                continue                  # saturated: shed, nothing leaked
            if m_pages:
                a.retain(m_pages)
                cache.touch(nodes)
            fresh = a.alloc(n_fresh)
            cache.insert(nodes[-1] if nodes else None, t,
                         len(m_pages) * PL, fresh)
            handles.append(m_pages + fresh)
        elif op <= 6 and handles:         # a group retires
            a.release(handles.pop(int(rng.integers(len(handles)))))
        elif op == 7:                     # pool pressure
            cache.evict(int(rng.integers(1, 4)))
        elif op == 8:                     # weight swap
            cache.flush()
        else:                             # drive round boundary
            cache.step()
            cache.reap()
        _check_cache_partition(a, cache, handles)

    while handles:
        a.release(handles.pop())
    cache.step()
    cache.evict(num_pages)
    _check_cache_partition(a, cache, [])
    assert cache.num_resident == 0
    assert a.num_free == num_pages, "drained pool did not return whole"


# ---------------------------------------- learner retention (DESIGN.md §11)
def test_learner_retention_survives_eviction_and_flush():
    """The zero re-prefill handoff: the learner takes its own ref on a
    harvested response's prompt pages.  Neither pool-pressure eviction nor
    the set_params epoch flush may reclaim them while that ref lives —
    only the learner's release makes them evictable."""
    a, cache = make()
    pages, _ = prefill_insert(a, cache, toks(1, 2))
    cache.step()
    a.retain(pages)                     # learner retains at harvest
    a.release(pages)                    # the rollout group retires
    assert cache.evict(8) == []         # pressure: retained pages survive
    assert cache.flush() == []          # weight swap: ditto
    assert all(int(a.refcount[p]) >= 1 for p in pages)
    a.release(pages)                    # learner releases after the step
    assert sorted(cache.reap() + cache.evict(8)) == sorted(pages)
    assert a.in_use == 0


@settings(max_examples=25)
@given(st.integers(min_value=6, max_value=24),
       st.lists(st.integers(min_value=0, max_value=11),
                min_size=20, max_size=60),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_retention_interleavings_never_leak_or_reclaim(
        num_pages, script, seed):
    """The ownership property test with the learner in the loop: groups
    place/retire as before, and harvests hand the group's pages to a
    learner handle (extra ref) that outlives eviction and flush.  After
    every op the pool still partitions exactly, and no retained page is
    ever on the free list.  Draining groups AND learner handles returns
    the whole pool."""
    rng = np.random.default_rng(seed)
    a = PageAllocator(num_pages)
    cache = RadixPrefixCache(a, PL)
    handles = []     # live rollout groups
    retained = []    # learner-retained page sets (one ref each)

    def new_prompt():
        n = int(rng.integers(1, 4))
        return np.asarray(rng.integers(0, 3, size=n * PL), np.int32)

    for op in script:
        if op <= 3:                       # place a group (engine commit)
            t = new_prompt()
            nodes = cache.lookup(t)
            m_pages = [nd.page for nd in nodes]
            n_fresh = len(t) // PL - len(m_pages)
            if n_fresh > a.num_free:
                cache.evict(n_fresh - a.num_free)
            if n_fresh > a.num_free:
                continue                  # saturated: shed, nothing leaked
            if m_pages:
                a.retain(m_pages)
                cache.touch(nodes)
            fresh = a.alloc(n_fresh)
            cache.insert(nodes[-1] if nodes else None, t,
                         len(m_pages) * PL, fresh)
            handles.append(m_pages + fresh)
        elif op <= 5 and handles:         # harvest: learner retains, group
            pages = handles.pop(int(rng.integers(len(handles))))
            a.retain(pages)               # retires in the same breath
            retained.append(pages)
            a.release(pages)
        elif op == 6 and handles:         # a group retires unharvested
            a.release(handles.pop(int(rng.integers(len(handles)))))
        elif op == 7 and retained:        # learner grad step done
            a.release(retained.pop(int(rng.integers(len(retained)))))
        elif op == 8:                     # pool pressure
            cache.evict(int(rng.integers(1, 4)))
        elif op == 9:                     # weight swap
            cache.flush()
        else:                             # drive round boundary
            cache.step()
            cache.reap()
        _check_cache_partition(a, cache, handles + retained)
        free = set(a._free)
        for pages in retained:
            assert free.isdisjoint(pages), "retained page was reclaimed"

    for pages in retained + handles:
        a.release(pages)
    cache.step()
    cache.flush()
    cache.reap()
    cache.evict(num_pages)
    _check_cache_partition(a, cache, [])
    assert a.num_free == num_pages, "drained pool did not return whole"
