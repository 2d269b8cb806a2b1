"""Continuous-batching rollout engine: a fixed slot arena with recycling.

The legacy path (``rl/rollout.py::generate``) scans every row for the full
``max_new_tokens`` budget, so a batch is as slow as its longest row — the
straggler bottleneck NAT's APRIL-style over-provisioning attacks.  This
engine keeps a fixed ``(num_slots, cache_len)`` KV arena instead: a row that
emits EOS (or exhausts its per-request budget) is *retired* immediately, its
outputs harvested, and its slot re-prefilled with the next queued prompt
while the other slots keep decoding (DESIGN.md §3).

One executable serves the whole run.  The jitted step takes static shapes
only — ``(R, Tp)`` refill lanes, ``(S,)`` masks — and does:

  1. deactivate cancelled slots (host-driven APRIL quota cancellation),
  2. ``lax.cond``-gated prefill of up to R refill lanes (R < S keeps refill
     FLOPs proportional to actual turnover, not arena width), scattered
     row-wise into the arena at their target slots so a retired slot's
     cache rows are fully overwritten before reuse,
  3. a ``lax.scan`` of ``steps_per_sync`` masked decode substeps collecting
     behaviour logprobs/entropies in flight (the GRPO scoring fusion of the
     legacy path, preserved).

Because slot state transitions are data (masks), no shape ever depends on
which rows retire — there are zero per-batch recompiles.  The host loop only
syncs two ``(S,)`` control planes per round; retire-detection latency is
bounded by ``steps_per_sync`` substeps.

Per-request token budgets make the engine double as the serving decode loop
(``examples/serve_decode.py``): requests carry their own ``max_tokens``, and
short requests stop paying for long neighbours.

The host side is a *session* API (DESIGN.md §6): ``begin`` installs params
and a fresh arena, ``submit`` enqueues requests at any time, ``drive`` runs
exactly one harvest/refill/step round and returns the completions it
retired, and ``set_params`` swaps in a new parameter snapshot for the
*next* dispatched step — the in-flight executable keeps the reference it
was called with, so weight publication never copies or races a running
step.  ``run`` is the run-to-completion wrapper over the same rounds; the
stream-overlapped trainer (``rl/async_trainer.py``) drives sessions
directly so rollouts from one policy version keep draining while the
learner steps the next.

Each round is traced as a ``nat.engine.round`` profiler span holding
``nat.engine.sync`` (the blocking read of the control planes: the wait for
the device), ``nat.engine.harvest``, ``nat.engine.place`` and
``nat.engine.dispatch``; the jitted step's parts carry ``engine.*`` name
scopes.  ``stats["sync_s"]`` and ``stats["host_s"]`` count the seconds of
the round spent in the sync and after it, always on.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import capabilities as caps
from repro.models.attention import gather_pages
from repro.models.config import ModelConfig
from repro.models.model import (
    cache_decl,
    decode_step,
    invalidate_cache_rows,
    invalidate_pages,
    paged_cache_decl,
    paged_prefill,
    prefill,
)
from repro.dist.publish import tree_bytes as _tree_bytes
from repro.rl.radix import RadixPrefixCache

Array = jax.Array
F32 = jnp.float32
span = jax.profiler.TraceAnnotation    # host span in the profiler's trace


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static arena geometry — part of the jit cache key."""

    num_slots: int = 8
    max_prompt_len: int = 32
    steps_per_sync: int = 4  # decode substeps per host round-trip
    refill_lanes: int = 0  # prefill width per step; 0 -> ceil(num_slots / 4)

    @property
    def lanes(self) -> int:
        return self.refill_lanes or max(1, -(-self.num_slots // 4))


@dataclasses.dataclass(frozen=True)
class Request:
    uid: int
    tokens: np.ndarray  # (Tp,) int32, unpadded prompt
    budget: int = 0  # max new tokens; 0 -> rollout config's max_new_tokens


@dataclasses.dataclass
class Completion:
    uid: int
    prompt_len: int
    tokens: np.ndarray  # (response_len,) generated tokens (incl. EOS if hit)
    logp: np.ndarray  # (response_len,) behaviour logprobs
    entropy: np.ndarray  # (response_len,) behaviour entropies
    completed: bool  # emitted EOS within budget
    cancelled: bool = False  # retired early by the caller (quota met)

    @property
    def response_len(self) -> int:
        return int(self.tokens.shape[0])


# --------------------------------------------------- shared substep pieces
def _substep_sample(st: dict, rcfg, n: int, s_slots: int):
    """Sample the next token from the current logits and record it for live
    slots — the head every arena substep (dense or paged) shares.  Mutates
    ``st`` in place (out_* planes + key) and returns (nxt, live)."""
    live = st["active"] & ~st["done"]
    key, k1 = jax.random.split(st["key"])
    if rcfg.temperature == 0.0:
        nxt = jnp.argmax(st["logits"], axis=-1)
    else:
        nxt = jax.random.categorical(
            k1, st["logits"] / rcfg.temperature, axis=-1)
    logp_all = jax.nn.log_softmax(st["logits"], axis=-1)
    logp = jnp.take_along_axis(logp_all, nxt[:, None], axis=-1)[:, 0]
    ent = -jnp.sum(jnp.exp(logp_all) * logp_all, axis=-1)
    nxt = jnp.where(live, nxt, rcfg.pad_id).astype(jnp.int32)

    bi = jnp.arange(s_slots)
    idx = jnp.minimum(st["n_gen"], n - 1)
    st["out_tok"] = st["out_tok"].at[bi, idx].set(
        jnp.where(live, nxt, st["out_tok"][bi, idx]))
    st["out_logp"] = st["out_logp"].at[bi, idx].set(
        jnp.where(live, logp, st["out_logp"][bi, idx]))
    st["out_ent"] = st["out_ent"].at[bi, idx].set(
        jnp.where(live, ent, st["out_ent"][bi, idx]))
    st["key"] = key
    return nxt, live


def _place_slot_planes(st: dict, tgt, lens, budgets, logits, n: int,
                       pad_id: int) -> dict:
    """Scatter freshly-placed slots' per-slot planes — shared by the paged
    step's prefill placement and parked-sibling resume: prompt logits in,
    counters zeroed, output buffers cleared.  ``tgt`` carries the
    slot-count sentinel for masked lanes (dropped)."""
    rg = tgt.shape[0]
    st["logits"] = st["logits"].at[tgt].set(logits.astype(F32), mode="drop")
    st["pos"] = st["pos"].at[tgt].set(lens, mode="drop")
    st["prompt_len"] = st["prompt_len"].at[tgt].set(lens, mode="drop")
    st["n_gen"] = st["n_gen"].at[tgt].set(0, mode="drop")
    st["budget"] = st["budget"].at[tgt].set(budgets, mode="drop")
    st["active"] = st["active"].at[tgt].set(True, mode="drop")
    st["done"] = st["done"].at[tgt].set(False, mode="drop")
    st["eos_hit"] = st["eos_hit"].at[tgt].set(False, mode="drop")
    st["out_tok"] = st["out_tok"].at[tgt].set(
        jnp.full((rg, n), pad_id, st["out_tok"].dtype), mode="drop")
    st["out_logp"] = st["out_logp"].at[tgt].set(
        jnp.zeros((rg, n), F32), mode="drop")
    st["out_ent"] = st["out_ent"].at[tgt].set(
        jnp.zeros((rg, n), F32), mode="drop")
    return st


def _substep_advance(st: dict, nxt, live, new_logits, rcfg) -> dict:
    """Shared substep tail: merge the new logits for live slots, advance the
    position/count planes, latch EOS/budget retirement."""
    st["logits"] = jnp.where(
        live[:, None], new_logits.astype(F32), st["logits"])
    st["pos"] = st["pos"] + live
    st["n_gen"] = st["n_gen"] + live
    hit_eos = live & (nxt == rcfg.eos_id)
    st["eos_hit"] = st["eos_hit"] | hit_eos
    st["done"] = st["done"] | (
        live & (hit_eos | (st["n_gen"] >= st["budget"])))
    return st


class ContinuousRolloutEngine:
    """Slot-arena decode over the same sharded params the learner updates.

    The engine is stateless between ``run`` calls; ``last_state`` keeps the
    final device state of the most recent run for arena introspection
    (tests assert the retire/refill invariants on it).
    """

    def __init__(self, cfg: ModelConfig, rcfg, ecfg: EngineConfig,
                 *, device=None):
        if cfg.num_codebooks:
            raise NotImplementedError("engine serves text LMs (no codebooks)")
        caps.check_engine(cfg, "continuous")
        if ecfg.lanes > ecfg.num_slots:
            raise ValueError("refill_lanes cannot exceed num_slots")
        self.cfg = cfg
        self.rcfg = rcfg
        self.ecfg = ecfg
        # slice pinning (DESIGN.md §12): with a device, params and arena
        # state are committed there, so every donated step — and the whole
        # session — runs on that slice regardless of where the caller's
        # arrays live.  None keeps the pre-fleet behaviour (default device).
        self._device = device
        self.cache_len = ecfg.max_prompt_len + rcfg.max_new_tokens
        # donate the state: the arena (the big buffer) is updated in place
        # instead of copied every round
        self._step = jax.jit(self._make_step(), donate_argnums=(1,))
        self._cache_tmpl = None  # abstract cache template, memoized per run
        self.last_state: Optional[dict] = None
        self.stats: dict = {}
        # fault-injection seam (testing/chaos.py, DESIGN.md §13): when a
        # FaultPlan is installed, drive() fires once per round with this
        # engine's replica tag — injected PagePoolExhausted here fakes
        # transient pool pressure for the trainer's bounded retry
        self.chaos = None
        self.chaos_replica: Optional[str] = None
        # session fields (installed by begin(); benign defaults so `idle`
        # and introspection work on a never-begun engine)
        self._params = None
        self._on_finish = None
        self._on_token = None
        self._streamed: list = [0] * ecfg.num_slots
        self._queue: collections.deque = collections.deque()
        self._slot_uid: list = [None] * ecfg.num_slots
        self._to_cancel: set = set()
        self._state: Optional[dict] = None

    # ------------------------------------------------------------ device side
    def _init_state(self, params, key: Array) -> dict:
        """Zeroed arena.  The cache template comes from an abstract prefill
        so storage dtype matches what refills actually produce (bit-exact
        logprob parity with the legacy path under f32 params), with
        ``cache_decl`` shapes as the contract."""
        s = self.ecfg.num_slots
        n = self.rcfg.max_new_tokens
        if self._cache_tmpl is None:  # abstract trace once per engine
            tmpl = jax.eval_shape(
                lambda p: prefill(
                    p, self.cfg,
                    jnp.zeros((s, self.ecfg.max_prompt_len), jnp.int32),
                    cache_len=self.cache_len,
                    prefill_len=jnp.ones((s,), jnp.int32))[1],
                params)
            decl = cache_decl(self.cfg, s, self.cache_len)

            def check(a, b):
                assert a.shape == b.shape, \
                    f"cache shape drift {a.shape}!={b.shape}"

            jax.tree.map(check, tmpl, decl)
            self._cache_tmpl = tmpl
        cache = jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype),
                             self._cache_tmpl)
        cache = invalidate_cache_rows(cache, jnp.ones((s,), bool))
        return {
            "cache": cache,
            "logits": jnp.zeros((s, self.cfg.vocab_size), F32),
            "pos": jnp.zeros((s,), jnp.int32),
            "prompt_len": jnp.zeros((s,), jnp.int32),
            "n_gen": jnp.zeros((s,), jnp.int32),
            "budget": jnp.zeros((s,), jnp.int32),
            "active": jnp.zeros((s,), bool),
            "done": jnp.zeros((s,), bool),
            "eos_hit": jnp.zeros((s,), bool),
            # copy: the state is donated to the step, and the caller's key
            # must survive this run
            "key": jnp.array(key),
            "out_tok": jnp.full((s, n), self.rcfg.pad_id, jnp.int32),
            "out_logp": jnp.zeros((s, n), F32),
            "out_ent": jnp.zeros((s, n), F32),
        }

    def _make_step(self):
        cfg, rcfg, ecfg = self.cfg, self.rcfg, self.ecfg
        s_slots = ecfg.num_slots
        n = rcfg.max_new_tokens
        cache_len = self.cache_len

        def engine_step(params, state, refill_toks, refill_lens,
                        refill_budgets, refill_slots, refill_mask,
                        cancel_mask):
            # refill_* are (R,) lanes; refill_slots names each lane's target
            # arena slot; masked-out lanes scatter nowhere (index S, dropped).
            st = dict(state)
            # 1. cancelled slots become free (harvest already happened on host)
            with jax.named_scope("engine.invalidate"):
                st["active"] = st["active"] & ~cancel_mask
                st["done"] = st["done"] & ~cancel_mask

            # 2. refill: R-wide prefill scattered into the arena at the
            # target slots.  lax.cond skips it on pure-decode rounds, and
            # R < S keeps prefill cost on turnover, not arena width.
            tgt = jnp.where(refill_mask, refill_slots, s_slots).astype(jnp.int32)

            def scat_rows(arena, rows):
                # arena (repeat, S, ...) <- rows (repeat, R, ...) at dim 1
                return arena.at[:, tgt].set(rows.astype(arena.dtype),
                                            mode="drop")

            def scat_plane(plane, vals):
                return plane.at[tgt].set(vals.astype(plane.dtype), mode="drop")

            def do_refill(st):
                st = dict(st)
                logits0, fresh = prefill(
                    params, cfg, refill_toks, cache_len=cache_len,
                    prefill_len=jnp.maximum(refill_lens, 1))
                st["cache"] = jax.tree.map(scat_rows, st["cache"], fresh)
                st["logits"] = st["logits"].at[tgt].set(
                    logits0.astype(F32), mode="drop")
                st["pos"] = scat_plane(st["pos"], refill_lens)
                st["prompt_len"] = scat_plane(st["prompt_len"], refill_lens)
                st["n_gen"] = scat_plane(st["n_gen"], jnp.zeros_like(refill_lens))
                st["budget"] = scat_plane(st["budget"], refill_budgets)
                ones = jnp.ones_like(refill_mask)
                st["active"] = scat_plane(st["active"], ones)
                st["done"] = scat_plane(st["done"], ~ones)
                st["eos_hit"] = scat_plane(st["eos_hit"], ~ones)
                r = refill_mask.shape[0]
                st["out_tok"] = st["out_tok"].at[tgt].set(
                    jnp.full((r, n), rcfg.pad_id, st["out_tok"].dtype),
                    mode="drop")
                st["out_logp"] = st["out_logp"].at[tgt].set(
                    jnp.zeros((r, n), F32), mode="drop")
                st["out_ent"] = st["out_ent"].at[tgt].set(
                    jnp.zeros((r, n), F32), mode="drop")
                return st

            with jax.named_scope("engine.prefill"):
                st = jax.lax.cond(refill_mask.any(), do_refill,
                                  lambda s: dict(s), st)

            # 3. masked decode substeps: retired/empty slots ride along (the
            # shapes are static) but emit nothing and hold their state.
            def substep(st, _):
                st = dict(st)
                with jax.named_scope("engine.sample"):
                    nxt, live = _substep_sample(st, rcfg, n, s_slots)
                new_logits, new_cache = decode_step(
                    params, cfg, nxt, st["cache"], st["pos"])
                st["cache"] = new_cache
                st = _substep_advance(st, nxt, live, new_logits, rcfg)
                return st, None

            with jax.named_scope("engine.decode"):
                st, _ = jax.lax.scan(substep, st, None,
                                     length=ecfg.steps_per_sync)
            return st

        return engine_step

    # ----------------------------------------------------- host side: session
    def begin(
        self,
        params,
        key: Array,
        *,
        on_finish: Optional[Callable[[Completion], Optional[Iterable[int]]]]
        = None,
        on_token: Optional[Callable[[int, np.ndarray], None]] = None,
    ) -> None:
        """Open a session: fresh arena, empty queue, zeroed stats.

        ``on_finish(completion)`` fires as each request retires (inside
        ``drive``) and may return uids to cancel — queued uids are dropped
        before placement, in-flight uids retire early with
        ``cancelled=True`` in the same round they are discovered.

        ``on_token(uid, tokens)`` streams incremental output: it fires at
        the top of each ``drive`` with the tokens a request generated
        since its last delivery (latency bounded by ``steps_per_sync``
        substeps), and a request's deltas always arrive before its
        Completion.  Streaming syncs two extra planes per round, so leave
        it off for pure-throughput rollout."""
        if self._device is not None:
            params = jax.device_put(params, self._device)
        self._params = params
        self._on_finish = on_finish
        self._on_token = on_token
        self._streamed = [0] * self.ecfg.num_slots
        self._queue: collections.deque = collections.deque()
        self._slot_uid: list = [None] * self.ecfg.num_slots
        self._to_cancel: set = set()
        state = self._init_state(params, key)
        if self._device is not None:
            state = jax.device_put(state, self._device)
        self._state = state
        self.stats = {"rounds": 0, "decode_steps": 0, "refills": 0,
                      "tokens_generated": 0, "cancelled": 0,
                      "slot_substeps": 0, "sync_s": 0.0, "host_s": 0.0}

    def _validate_requests(self, requests: Sequence[Request]) -> None:
        rcfg, tp = self.rcfg, self.ecfg.max_prompt_len
        for r in requests:
            if len(r.tokens) > tp:
                raise ValueError(f"request {r.uid}: prompt longer than {tp}")
            if r.budget > rcfg.max_new_tokens:
                raise ValueError(f"request {r.uid}: budget > max_new_tokens")

    def submit(self, requests: Sequence[Request]) -> None:
        """Enqueue requests; callable at any point during a session, so new
        work streams in while earlier rollouts are still draining."""
        self._validate_requests(requests)
        self._queue.extend(requests)

    def submit_group(self, requests: Sequence[Request]) -> None:
        """Enqueue one GRPO group's sibling requests.  The dense arena has
        no prompt sharing, so this is plain ``submit``; the paged engine
        overrides it to prefill the shared prompt once (DESIGN.md §8).
        Call sites that know the group structure should use this."""
        self.submit(requests)

    def set_params(self, params) -> None:
        """Versioned snapshot swap: the *next* dispatched step decodes under
        ``params``.  The step already in flight keeps the reference it was
        called with (jax arrays are immutable), so no copy and no race.
        On a slice-pinned engine the snapshot is committed to the slice
        (a no-op when the publisher already delivered it there)."""
        if self._device is not None:
            params = jax.device_put(params, self._device)
        self._params = params

    def cancel(self, uids: Iterable[int]) -> None:
        """Mark uids for cancellation, handled at the next ``drive``."""
        self._to_cancel.update(uids)

    @property
    def idle(self) -> bool:
        """No queued work and every slot free — ``drive`` would be a no-op."""
        return not self._queue and all(u is None for u in self._slot_uid)

    @property
    def backlog(self) -> int:
        """Accepted-but-unplaced work units queued on the host — the
        admission signal the serving front-end throttles on."""
        return len(self._queue)

    def _harvest(self, s: int, host, cancelled: bool) -> Completion:
        uid = self._slot_uid[s]
        rl = int(host["n_gen"][s])
        comp = Completion(
            uid=uid,
            prompt_len=int(host["prompt_len"][s]),
            tokens=host["out_tok"][s, :rl].copy(),
            logp=host["out_logp"][s, :rl].copy(),
            entropy=host["out_ent"][s, :rl].copy(),
            completed=bool(host["eos_hit"][s]) and not cancelled,
            cancelled=cancelled)
        self._slot_uid[s] = None
        self.stats["tokens_generated"] += rl
        if cancelled:
            self.stats["cancelled"] += 1
        if self._on_finish is not None:
            self._to_cancel.update(self._on_finish(comp) or ())
        return comp

    def _collect_retirements(self) -> tuple:
        """Sync the control planes and harvest every retired or cancelled
        slot.  Returns (harvested Completions, device cancel_mask (S,)) —
        the round head shared by the dense and paged drive loops.  The
        sync is the round's wait for the device (``stats["sync_s"]``);
        the round's host time counts from its return."""
        state = self._state
        with span("nat.engine.sync"):
            t0 = time.perf_counter()
            active = np.asarray(state["active"])
            done = np.asarray(state["done"])
            self._t_synced = time.perf_counter()
        self.stats["sync_s"] += self._t_synced - t0
        with span("nat.engine.harvest"):
            return self._harvest_round(state, active, done)

    def _harvest_round(self, state, active, done) -> tuple:
        """``_collect_retirements`` after the sync: stream, fetch and
        harvest, given the synced ``active`` / ``done`` planes."""
        slot_uid = self._slot_uid
        to_cancel = self._to_cancel
        s_slots = self.ecfg.num_slots
        harvested: list = []

        # -- streaming: deliver each live slot's new tokens before any
        # harvest below, so a request's deltas always precede its finish
        if self._on_token is not None and any(
                u is not None for u in slot_uid):
            n_gen_h = np.asarray(state["n_gen"])
            out_tok_h = np.asarray(state["out_tok"])
            for s in range(s_slots):
                if slot_uid[s] is None:
                    continue
                k = int(n_gen_h[s])
                if k > self._streamed[s]:
                    self._on_token(
                        slot_uid[s],
                        out_tok_h[s, self._streamed[s]:k].copy())
                    self._streamed[s] = k

        # -- fetch buffers only on retirement
        retired = [s for s in range(s_slots)
                   if slot_uid[s] is not None and active[s] and done[s]]
        cancel_mask = np.zeros((s_slots,), bool)
        host = None
        need_fetch = bool(retired) or any(
            u in to_cancel for u in slot_uid if u is not None)
        if need_fetch:
            host = {k: np.asarray(state[k]) for k in
                    ("n_gen", "prompt_len", "eos_hit",
                     "out_tok", "out_logp", "out_ent")}
        # snapshot cancel state first: rows in `retired` finished on
        # their own (EOS/budget), so cancellations issued by on_finish
        # callbacks *during* this harvest loop must not relabel them
        was_cancelled = {s: slot_uid[s] in to_cancel for s in retired}
        for s in retired:
            harvested.append(self._harvest(s, host, was_cancelled[s]))
            cancel_mask[s] = True  # clears active/done on device
        # quota-cancel rows still decoding (including cancellations the
        # on_finish callbacks just issued): retire them as partials now
        if host is not None:
            for s in range(s_slots):
                if slot_uid[s] is not None and slot_uid[s] in to_cancel:
                    harvested.append(self._harvest(s, host, True))
                    cancel_mask[s] = True
        return harvested, cancel_mask

    def _cancelled_completion(self, r: Request) -> Completion:
        """Empty Completion for a request cancelled before placement.  The
        contract fires on_finish for every request, including these."""
        comp = Completion(
            uid=r.uid, prompt_len=len(r.tokens),
            tokens=np.zeros((0,), np.int32),
            logp=np.zeros((0,), np.float32),
            entropy=np.zeros((0,), np.float32),
            completed=False, cancelled=True)
        self.stats["cancelled"] += 1
        if self._on_finish is not None:
            self._to_cancel.update(self._on_finish(comp) or ())
        return comp

    def drive(self) -> list:
        """One round: sync the control planes, harvest retirements, refill
        free slots from the queue, dispatch the jitted step.  Returns the
        Completions retired this round (possibly empty).  When the session
        is idle the call is a no-op."""
        with span("nat.engine.round"):
            harvested = self._round()
            self.stats["host_s"] += time.perf_counter() - self._t_synced
        return harvested

    def _round(self) -> list:
        """The round itself; ``drive`` holds its span and its counters."""
        if self.chaos is not None:
            self.chaos.fire("drive", replica=self.chaos_replica,
                            index=self.stats.get("rounds", 0))
        ecfg, rcfg = self.ecfg, self.rcfg
        s_slots, tp = ecfg.num_slots, ecfg.max_prompt_len
        state, slot_uid, queue = self._state, self._slot_uid, self._queue
        to_cancel = self._to_cancel
        harvested, cancel_mask = self._collect_retirements()

        # -- refill free slots from the queue (skipping cancelled uids),
        # at most R lanes per round
        with span("nat.engine.place"):
            lanes = ecfg.lanes
            refill_mask = np.zeros((lanes,), bool)
            refill_toks = np.full((lanes, tp), rcfg.pad_id, np.int32)
            refill_lens = np.ones((lanes,), np.int32)
            refill_budgets = np.zeros((lanes,), np.int32)
            refill_slots = np.zeros((lanes,), np.int32)
            lane = 0
            for s in range(s_slots):
                if slot_uid[s] is not None or lane >= lanes:
                    continue
                while queue and queue[0].uid in to_cancel:
                    harvested.append(
                        self._cancelled_completion(queue.popleft()))
                if not queue:
                    break
                r = queue.popleft()
                pl = len(r.tokens)
                refill_toks[lane, :pl] = r.tokens
                refill_lens[lane] = pl
                refill_budgets[lane] = r.budget or rcfg.max_new_tokens
                refill_slots[lane] = s
                refill_mask[lane] = True
                slot_uid[s] = r.uid
                self._streamed[s] = 0
                lane += 1

        if not refill_mask.any() and all(u is None for u in slot_uid):
            self.last_state = state  # session quiescent: expose for tests
            return harvested

        with span("nat.engine.dispatch"):
            self._state = self._step(
                self._params, state, jnp.asarray(refill_toks),
                jnp.asarray(refill_lens), jnp.asarray(refill_budgets),
                jnp.asarray(refill_slots), jnp.asarray(refill_mask),
                jnp.asarray(cancel_mask))
        self.stats["rounds"] += 1
        self.stats["decode_steps"] += ecfg.steps_per_sync
        self.stats["slot_substeps"] += ecfg.steps_per_sync * s_slots
        self.stats["refills"] += int(refill_mask.sum())
        return harvested

    def drain(self) -> list:
        """Drive rounds until the session is idle; returns all Completions
        harvested along the way."""
        out: list = []
        while True:
            got = self.drive()
            out.extend(got)
            if self.idle and not got:
                return out

    def run_groups(
        self,
        params,
        groups: Sequence[Sequence[Request]],
        key: Array,
        *,
        on_finish: Optional[Callable[[Completion], Optional[Iterable[int]]]]
        = None,
    ) -> list:
        """Serve ``groups`` (one ``submit_group`` each) to completion;
        returns Completions in submission order.  The group-aware
        run-to-completion wrapper shared by ``rollout_group_continuous``,
        the benchmarks, and the serving example — on the paged arena each
        group's prompt pages are shared across its siblings."""
        self.begin(params, key, on_finish=on_finish)
        for g in groups:
            self.submit_group(g)
        out = {c.uid: c for c in self.drain()}
        self.last_state = self._state
        return [out[r.uid] for g in groups for r in g if r.uid in out]

    def run(
        self,
        params,
        requests: Sequence[Request],
        key: Array,
        *,
        on_finish: Optional[Callable[[Completion], Optional[Iterable[int]]]]
        = None,
    ) -> list:
        """Serve ungrouped ``requests`` through the arena; returns
        Completions in submission order (``run_groups`` with singleton
        groups — identical FIFO submission on the dense arena)."""
        return self.run_groups(params, [[r] for r in requests], key,
                               on_finish=on_finish)


def make_engine(cfg: ModelConfig, rcfg, *, num_slots: int,
                max_prompt_len: int, steps_per_sync: int = 4,
                ) -> ContinuousRolloutEngine:
    return ContinuousRolloutEngine(
        cfg, rcfg, EngineConfig(num_slots=num_slots,
                                max_prompt_len=max_prompt_len,
                                steps_per_sync=steps_per_sync))


# ======================================================= paged KV arena
@dataclasses.dataclass(frozen=True)
class PagedEngineConfig:
    """Static geometry of the paged arena (DESIGN.md §8).

    The KV store is a fixed ``(num_pages, page_len)`` pool per attention
    layer plus per-slot block tables; a GRPO group's prompt pages are
    prefilled once and refcounted across all its siblings, so prompt KV
    memory per group is O(1) in the group size instead of O(G).
    """

    num_slots: int = 8
    max_prompt_len: int = 32
    steps_per_sync: int = 4    # decode substeps per host round-trip
    page_len: int = 16         # tokens per KV page
    num_pages: int = 0         # pool size; 0 -> dense-equivalent worst case
    group_lanes: int = 1       # groups prefilled per round
    max_group: int = 8         # widest group submit_group accepts
    resume_lanes: int = 0      # parked siblings placed per round; 0 -> auto
    attn_impl: str = "ref"     # "ref" (jnp gather) | "kernel" (Pallas)
    # cross-request radix prefix cache (DESIGN.md §10): longest-prefix
    # match reuses resident read-only pages, only the suffix prefills,
    # full suffix pages are chained back into the trie, and cold branches
    # are LRU-evicted under pool pressure instead of raising.  Off by
    # default: RL rollout re-prefills under fresh params every sync, so
    # only fixed-params serving benefits (pure-attention configs only —
    # see capabilities.check_prefix_cache).
    prefix_cache: bool = False
    # zero re-prefill learner handoff (DESIGN.md §11): every harvested
    # completion's prompt pages take an extra refcount reference so the
    # learner can score straight from the pool (export_learner_pages);
    # the reference survives radix eviction and the set_params epoch
    # flush, and is dropped by release_learner_pages after the grad step.
    # Pure-attention configs only (capabilities.check_paged_score).
    learner_retain: bool = False

    @property
    def lanes(self) -> int:
        return self.group_lanes

    @property
    def resumes(self) -> int:
        """Resume lane width: bounds the (lanes, vocab) logits operand
        shipped to the step each round, so it stays a group's worth, not
        an arena's worth."""
        return self.resume_lanes or max(1, min(self.num_slots,
                                               self.max_group))


class PagePoolExhausted(RuntimeError):
    """The page pool cannot satisfy an allocation.

    Raised eagerly on the host — never silently corrupting the arena —
    with the pool occupancy in the message.  Fix by growing ``num_pages``
    (the auto default of ``num_slots * pages_per_slot`` can never
    exhaust) or shrinking slots/budgets.
    """


class PageAllocator:
    """Host-side free list + refcounts over the device page pool.

    Pages are a shared resource: a GRPO group's prompt pages carry one
    reference per live sibling and are freed when the last sibling
    retires; decode pages are slot-private (refcount 1) and return to the
    free list the moment their slot retires or is cancelled.  The
    allocator only does bookkeeping — the device learns about reuse via
    the engine's free-page invalidation mask.
    """

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, -1, -1))  # LIFO stack
        self.refcount = np.zeros((num_pages,), np.int32)
        self.peak_in_use = 0

    @property
    def in_use(self) -> int:
        return self.num_pages - len(self._free)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int, what: str = "") -> list:
        if n > len(self._free):
            raise PagePoolExhausted(
                f"page pool exhausted allocating {n} page(s){what}: "
                f"{self.in_use}/{self.num_pages} pages in use "
                f"({len(self._free)} free); grow PagedEngineConfig.num_pages "
                "or reduce num_slots / budgets")
        pages = [self._free.pop() for _ in range(n)]
        self.refcount[pages] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pages

    def retain(self, pages: Sequence[int]) -> None:
        self.refcount[list(pages)] += 1

    def release(self, pages: Sequence[int]) -> list:
        """Drop one reference per page; returns the pages actually freed
        (refcount hit zero) — these need invalidation before reuse."""
        freed = []
        for p in pages:
            self.refcount[p] -= 1
            assert self.refcount[p] >= 0, f"page {p} over-released"
            if self.refcount[p] == 0:
                self._free.append(p)
                freed.append(p)
        return freed


class PagedRolloutEngine(ContinuousRolloutEngine):
    """Slot arena over a paged KV pool with group-level prefix sharing.

    Same session API and retire/refill discipline as the dense arena, with
    the memory model rewritten (DESIGN.md §8):

    * attention KV lives in a fixed ``(num_pages, page_len)`` pool per
      layer; per-slot structure is a host-built block table passed into
      every round — retiring a slot is a free-list push, not a row
      invalidation,
    * ``submit_group`` registers a GRPO group: the shared prompt is
      prefilled ONCE into refcounted read-only pages and every sibling's
      block table starts with them (decode tokens always open a fresh
      slot-private page, so copy-on-write is never needed),
    * siblings placed in the prefill round get the prompt logits and the
      O(window)/O(1) non-attention states broadcast on device; for
      pure-attention configs the remaining siblings are PARKED — the
      prompt logits persist in a ``prefill_logits`` state plane, the host
      snapshots them one round later, and each parked sibling resumes
      into any freed slot with a pure scatter (prompt pages + saved
      logits ARE the prompt state; nothing recomputes, so group width
      never serializes the arena).  Configs with per-slot sequence state
      (local rings, ssm/rec) place atomically instead,
    * APRIL cancellation frees a straggler's decode pages the moment the
      host learns of it; freed pages are ``pos``-poisoned on device before
      any reuse (gather isolation),
    * page allocation is host-side and allocate-ahead: before each round
      every occupied slot owns enough decode pages for ``steps_per_sync``
      tokens, so the jitted step never allocates; exhaustion raises
      ``PagePoolExhausted`` instead of corrupting the arena.
    """

    def __init__(self, cfg: ModelConfig, rcfg, ecfg: PagedEngineConfig,
                 *, device=None):
        caps.check_paged(cfg)
        if ecfg.prefix_cache:
            caps.check_prefix_cache(cfg)
        if ecfg.learner_retain:
            caps.check_paged_score(cfg)
        pl_ = ecfg.page_len
        self._n_pp = -(-ecfg.max_prompt_len // pl_)    # max prompt pages
        self._n_dp = -(-rcfg.max_new_tokens // pl_)    # max decode pages
        self._max_pages = self._n_pp + self._n_dp      # block table width
        self.num_pages = ecfg.num_pages or ecfg.num_slots * self._max_pages
        # deferred sibling placement needs the prompt state to live wholly
        # in shared pages + saved logits: true only for pure pool-resident
        # stacks (capability table shared_prefix_ok: attn full KV, mla
        # latents; local rings / ssm / rec carry per-slot sequence state)
        self._pure_pool = caps.pure_pool_prefix(cfg)
        if not self._pure_pool and ecfg.max_group > ecfg.num_slots:
            raise ValueError(
                "max_group cannot exceed num_slots: per-slot-state mixers "
                "(local/ssm/rec) place groups atomically")
        super().__init__(cfg, rcfg, ecfg, device=device)
        self._reset_pool()

    # ------------------------------------------------------------ host pool
    def _reset_pool(self) -> None:
        s = self.ecfg.num_slots
        self._alloc = PageAllocator(self.num_pages)
        self._slot_prompt_pages: list = [[] for _ in range(s)]
        self._slot_decode_pages: list = [[] for _ in range(s)]
        self._slot_plen = np.zeros((s,), np.int32)
        self._slot_budget = np.zeros((s,), np.int32)
        self._n_gen_ub = np.zeros((s,), np.int64)  # host upper bound on n_gen
        self._dirty: set = set()  # freed pages awaiting pos-invalidation
        # partially-placed groups: prompt prefilled, some siblings parked
        # awaiting a free slot; each record holds one extra prompt-page
        # reference until its last sibling places or cancels
        self._pending: list = []
        # learner-retained prompt pages: uid -> (pages, prompt_len); each
        # record holds one refcount reference (taken at harvest) until
        # release_learner_pages drops it
        self._retained: dict = {}
        self._prefix_cache = (RadixPrefixCache(self._alloc, self.ecfg.page_len)
                              if self.ecfg.prefix_cache else None)

    def begin(self, params, key: Array, *, on_finish=None,
              on_token=None) -> None:
        super().begin(params, key, on_finish=on_finish, on_token=on_token)
        self._reset_pool()
        self.stats.update(prompt_prefills=0, pages_in_use=0,
                          peak_pages_in_use=0, prompt_tokens=0,
                          prefill_tokens=0, prefix_hit_tokens=0,
                          evicted_pages=0)

    def set_params(self, params) -> None:
        """Weight swap invalidates every cached prefix: resident KV was
        computed under the old params.  Evictable branches free at once;
        branches with live readers drain via ``reap()``."""
        super().set_params(params)
        if self._prefix_cache is not None:
            self._dirty.update(self._prefix_cache.flush())

    def _ensure_free(self, n: int) -> bool:
        """Make >= ``n`` pages available, LRU-evicting cold radix branches
        under pressure; False when the pool still cannot satisfy it."""
        short = n - self._alloc.num_free
        if short > 0 and self._prefix_cache is not None:
            freed = self._prefix_cache.evict(short)
            self._dirty.update(freed)
            self.stats["evicted_pages"] += len(freed)
        return self._alloc.num_free >= n

    def _free_slot_pages(self, s: int) -> None:
        freed = self._alloc.release(self._slot_decode_pages[s])
        freed += self._alloc.release(self._slot_prompt_pages[s])
        self._dirty.update(freed)
        self._slot_decode_pages[s] = []
        self._slot_prompt_pages[s] = []

    def _harvest(self, s: int, host, cancelled: bool) -> Completion:
        comp = super()._harvest(s, host, cancelled)
        if self.ecfg.learner_retain:
            # take the learner's reference BEFORE the slot's own refs drop:
            # the prompt pages stay resident (and read-only — nothing
            # rewrites a page whose refcount is nonzero) until
            # release_learner_pages, surviving radix eviction and the
            # set_params epoch flush
            ppages = list(self._slot_prompt_pages[s])
            self._alloc.retain(ppages)
            self._retained[comp.uid] = (ppages, int(self._slot_plen[s]))
        self._free_slot_pages(s)
        return comp

    # -------------------------------------------------- learner page handoff
    def export_learner_pages(self, uids: Sequence) -> dict:
        """Slice the retained prompt pages of ``uids`` out of the pool for
        zero re-prefill scoring (DESIGN.md §11).

        Returns ``{"pool": tree, "block_tables": (len(uids), M) int32,
        "prompt_lens": (len(uids),) int32}`` where ``pool`` mirrors the
        cache layout per attention layer (``{"k"/"v": (repeat, P',
        page_len, KV, D), "pos": (repeat, P', page_len)}``) over the
        COMPACTED union of the requested pages, and ``block_tables`` is
        renumbered into it (-1 padded).  Pages shared by GRPO siblings
        appear once.  Feed straight into ``score_tokens(paged_prefix=
        pool, page_tables=...)`` with a ``PagedLayout`` batch whose
        segment order matches ``uids``.

        Host-side copy (``jnp.take``): must run between ``drive()`` calls
        — the live state is donated into the next jitted step.  Raises
        ``KeyError`` for a uid that was never harvested under
        ``learner_retain=True`` (e.g. cancelled before placement).
        """
        caps.check_paged_score(self.cfg)
        recs = [self._retained[uid] for uid in uids]
        pages_used: list = []
        index: dict = {}
        tables = np.full((len(recs), self._n_pp), -1, np.int32)
        plens = np.zeros((len(recs),), np.int32)
        for i, (ppages, plen) in enumerate(recs):
            plens[i] = plen
            for k, p in enumerate(ppages):
                if p not in index:
                    index[p] = len(pages_used)
                    pages_used.append(p)
                tables[i, k] = index[p]
        sel = jnp.asarray(np.asarray(pages_used or [0], np.int32))
        pool = {}
        for gi, (pattern, _repeat) in enumerate(self.cfg.blocks):
            grp = {}
            for j, _kind in enumerate(pattern):
                e = self._state["cache"][f"group{gi}"][f"l{j}"]
                grp[f"l{j}"] = {key: jnp.take(e[key], sel, axis=1)
                                for key in ("k", "v", "pos")}
            pool[f"group{gi}"] = grp
        return {"pool": pool, "block_tables": jnp.asarray(tables),
                "prompt_lens": plens}

    def release_learner_pages(self, uids: Optional[Sequence] = None) -> None:
        """Drop the learner references taken at harvest (all of them when
        ``uids`` is None) — call after the grad step consumed the export.
        Pages whose refcount hits zero rejoin the free list and are
        pos-poisoned before reuse, exactly like any other release."""
        keys = list(self._retained) if uids is None else list(uids)
        for uid in keys:
            pages, _plen = self._retained.pop(uid)
            self._dirty.update(self._alloc.release(pages))

    # ------------------------------------------------------------- submit
    def submit(self, requests: Sequence[Request]) -> None:
        """Ungrouped requests: each becomes its own group of one (no
        sharing, but the paged lifecycle still applies)."""
        for r in requests:
            self.submit_group([r])

    def submit_group(self, requests: Sequence[Request]) -> None:
        """Enqueue one group: siblings share a single prompt whose pages
        are prefilled once and refcounted across all of them."""
        reqs = list(requests)
        if not reqs:
            return
        if len(reqs) > self.ecfg.max_group:
            raise ValueError(
                f"group of {len(reqs)} exceeds max_group="
                f"{self.ecfg.max_group}")
        self._validate_requests(reqs)
        t0 = np.asarray(reqs[0].tokens)
        for r in reqs[1:]:
            if not np.array_equal(np.asarray(r.tokens), t0):
                raise ValueError(
                    "submit_group: siblings must share one prompt "
                    f"(uid {r.uid} differs from uid {reqs[0].uid})")
        pl_, n = self.ecfg.page_len, self.rcfg.max_new_tokens
        # worst-case CONCURRENT need: prompt pages once, plus decode pages
        # for the largest siblings that can run at the same time (parking
        # bounds concurrency by the slot count)
        dp = sorted((-(-(r.budget or n) // pl_) for r in reqs), reverse=True)
        need = -(-len(t0) // pl_) + sum(dp[:self.ecfg.num_slots])
        if need > self.num_pages:
            raise PagePoolExhausted(
                f"group needs up to {need} concurrent pages but the pool "
                f"holds only {self.num_pages}; grow "
                "PagedEngineConfig.num_pages")
        self._queue.append(reqs)

    # ------------------------------------------------------------ device side
    def _init_state(self, params, key: Array) -> dict:
        """Zeroed pool + per-slot planes.  Pool storage dtype comes from an
        abstract ``paged_prefill`` (what refills actually produce), with
        ``paged_cache_decl`` shapes as the contract; every page starts
        pos-poisoned (-1 = empty)."""
        ecfg = self.ecfg
        s, n = ecfg.num_slots, self.rcfg.max_new_tokens
        pl_, npg = ecfg.page_len, self.num_pages
        cfg = self.cfg
        if self._cache_tmpl is None:
            raw = jax.eval_shape(
                lambda p: paged_prefill(
                    p, cfg,
                    jnp.zeros((ecfg.group_lanes, ecfg.max_prompt_len),
                              jnp.int32),
                    cache_len=self.cache_len,
                    prefill_len=jnp.ones((ecfg.group_lanes,), jnp.int32))[1],
                params)
            decl = paged_cache_decl(cfg, s, self.cache_len,
                                    num_pages=npg, page_len=pl_)
            tmpl = {}
            for gi, (pattern, repeat) in enumerate(cfg.blocks):
                layer = {}
                for j, kind in enumerate(pattern):
                    e = raw[f"group{gi}"][f"l{j}"]
                    mixer = cfg.mixer_of(kind)
                    if mixer == "attn":
                        kvh, dh = e["k"].shape[-2:]
                        layer[f"l{j}"] = {
                            "k": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_, kvh, dh), e["k"].dtype),
                            "v": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_, kvh, dh), e["v"].dtype),
                            "pos": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_), jnp.int32),
                        }
                    elif mixer == "mla":
                        layer[f"l{j}"] = {
                            "c_kv": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_, e["c_kv"].shape[-1]),
                                e["c_kv"].dtype),
                            "k_rope": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_, e["k_rope"].shape[-1]),
                                e["k_rope"].dtype),
                            "pos": jax.ShapeDtypeStruct(
                                (repeat, npg, pl_), jnp.int32),
                        }
                    else:
                        # per-slot entry: widen the lane batch dim to S
                        layer[f"l{j}"] = jax.tree.map(
                            lambda d: jax.ShapeDtypeStruct(
                                (d.shape[0], s) + d.shape[2:], d.dtype), e)
                tmpl[f"group{gi}"] = layer

            def check(a, b):
                assert a.shape == b.shape, \
                    f"paged cache shape drift {a.shape}!={b.shape}"

            jax.tree.map(check, tmpl, decl)
            self._cache_tmpl = tmpl
        cache = jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype),
                             self._cache_tmpl)
        cache = invalidate_pages(cfg, cache, jnp.ones((npg,), bool))
        return {
            "cache": cache,
            # prompt logits of the last prefill, per lane: survives the
            # round so the host can snapshot them for parked siblings
            "prefill_logits": jnp.zeros(
                (ecfg.group_lanes, self.cfg.vocab_size), F32),
            "logits": jnp.zeros((s, self.cfg.vocab_size), F32),
            "pos": jnp.zeros((s,), jnp.int32),
            "prompt_len": jnp.zeros((s,), jnp.int32),
            "n_gen": jnp.zeros((s,), jnp.int32),
            "budget": jnp.zeros((s,), jnp.int32),
            "active": jnp.zeros((s,), bool),
            "done": jnp.zeros((s,), bool),
            "eos_hit": jnp.zeros((s,), bool),
            "key": jnp.array(key),
            "out_tok": jnp.full((s, n), self.rcfg.pad_id, jnp.int32),
            "out_logp": jnp.zeros((s, n), F32),
            "out_ent": jnp.zeros((s, n), F32),
        }

    def _make_step(self, external_prefill: bool = False):
        cfg, rcfg, ecfg = self.cfg, self.rcfg, self.ecfg
        s_slots = ecfg.num_slots
        n = rcfg.max_new_tokens
        tp = ecfg.max_prompt_len
        pl_ = ecfg.page_len
        npg = self.num_pages
        n_pp, max_pages = self._n_pp, self._max_pages
        gmax = ecfg.max_group
        pad_t = n_pp * pl_
        cache_len = self.cache_len
        attn_impl = ecfg.attn_impl
        use_prefix = ecfg.prefix_cache
        # external prefill (DESIGN.md §12): the prompt prefill ran on the
        # prefill slice; this step receives its (logits0, fresh KV) as
        # trailing operands and only scatters — state stays operand 1, so
        # donate_argnums is unchanged.  Incompatible with the radix prefix
        # cache (the match would need pool pages from the decode slice
        # inside the prefill computation).
        assert not (external_prefill and use_prefix), \
            "prefix_cache cannot span the prefill/decode split"

        def paged_engine_step(params, state, block_tables, free_page_mask,
                              refill_toks, refill_lens, refill_prefix_len,
                              refill_prefix_bt, refill_page_ids,
                              refill_slots, refill_budgets, refill_mask,
                              resume_slots, resume_logits, resume_lens,
                              resume_budgets, resume_mask, cancel_mask,
                              *handoff):
            st = dict(state)
            with jax.named_scope("engine.invalidate"):
                # 1. cancelled slots become free (harvest happened on host)
                st["active"] = st["active"] & ~cancel_mask
                st["done"] = st["done"] & ~cancel_mask
                # 2. pos-poison freed pages before any reuse this round: a
                # recycled page must never leak its previous occupant's
                # positions as valid entries (gather isolation)
                st["cache"] = invalidate_pages(cfg, st["cache"],
                                               free_page_mask)

            # 3. group refill: one prompt prefill per lane, its raw KV
            # scattered into the shared prompt pages, logits and per-slot
            # (non-attention) states broadcast to every sibling slot
            tgt = jnp.where(refill_slots < s_slots, refill_slots,
                            s_slots).astype(jnp.int32).reshape(-1)  # (R*Gmax,)
            flat_pages = jnp.minimum(refill_page_ids,
                                     npg).astype(jnp.int32).reshape(-1)

            def do_refill(st):
                st = dict(st)
                # radix prefix resume: gather the matched pages' K/V per
                # layer (post-invalidation, so evicted pages are already
                # invisible) and prefill only the unmatched suffix; the
                # scatter below lands suffix K/V in the fresh pages with
                # positions offset past the cached prefix.  With the cache
                # off, refill_prefix_len is all-zero and this is exactly
                # the old full-prompt prefill.
                if external_prefill:
                    # computed on the prefill slice, shipped device-to-
                    # device by _dispatch; zero-filled buffers on
                    # pure-decode rounds (branch result unused)
                    logits0, fresh = handoff
                elif use_prefix:
                    pfx = {}
                    for gi, (pattern, _repeat) in enumerate(cfg.blocks):
                        grp_p = {}
                        for j, _kind in enumerate(pattern):
                            e = st["cache"][f"group{gi}"][f"l{j}"]
                            kg, vg, posg = jax.vmap(
                                gather_pages, in_axes=(0, None))(
                                    {"k": e["k"], "v": e["v"],
                                     "pos": e["pos"]}, refill_prefix_bt)
                            grp_p[f"l{j}"] = {"k": kg, "v": vg, "pos": posg}
                        pfx[f"group{gi}"] = grp_p
                    logits0, fresh = paged_prefill(
                        params, cfg, refill_toks, cache_len=cache_len,
                        prefill_len=jnp.maximum(refill_lens, 1),
                        prefix_kv=pfx, prefix_len=refill_prefix_len)
                else:
                    logits0, fresh = paged_prefill(
                        params, cfg, refill_toks, cache_len=cache_len,
                        prefill_len=jnp.maximum(refill_lens, 1),
                        prefix_kv=None, prefix_len=None)
                qpos = jnp.arange(pad_t)[None, :]
                page_vals = jnp.where(
                    qpos < refill_lens[:, None],
                    refill_prefix_len[:, None] + qpos, -1).astype(jnp.int32)
                page_vals = page_vals.reshape(-1, pl_)       # (R*n_pp, pl)

                new_cache = {}
                for gi, (pattern, repeat) in enumerate(cfg.blocks):
                    grp = {}
                    for j, kind in enumerate(pattern):
                        e_old = st["cache"][f"group{gi}"][f"l{j}"]
                        e_new = fresh[f"group{gi}"][f"l{j}"]
                        if caps.pool_resident(cfg.mixer_of(kind)):
                            def scat_pool(pool, raw):
                                # raw (repeat, R, Tp, *feat) -> page blocks
                                # (attn: KV, D feature dims; mla: R / Dr)
                                raw = jnp.pad(
                                    raw, ((0, 0), (0, 0), (0, pad_t - tp))
                                    + ((0, 0),) * (raw.ndim - 3))
                                rep, r_ = raw.shape[:2]
                                raw = raw.reshape(rep, r_ * n_pp, pl_,
                                                  *raw.shape[3:])
                                return pool.at[:, flat_pages].set(
                                    raw.astype(pool.dtype), mode="drop")

                            rep = e_old["pos"].shape[0]
                            pos_new = e_old["pos"].at[:, flat_pages].set(
                                jnp.broadcast_to(
                                    page_vals, (rep,) + page_vals.shape),
                                mode="drop")
                            entry = {key: scat_pool(e_old[key], e_new[key])
                                     for key in e_new}
                            entry["pos"] = pos_new
                            grp[f"l{j}"] = entry
                        else:
                            def scat_slot(arena, rows):
                                rows = jnp.repeat(rows, gmax, axis=1)
                                return arena.at[:, tgt].set(
                                    rows.astype(arena.dtype), mode="drop")

                            grp[f"l{j}"] = jax.tree.map(scat_slot, e_old,
                                                        e_new)
                    new_cache[f"group{gi}"] = grp
                st["cache"] = new_cache

                st["prefill_logits"] = logits0.astype(F32)
                full_lens = refill_prefix_len + refill_lens
                return _place_slot_planes(
                    st, tgt, jnp.repeat(full_lens, gmax),
                    refill_budgets.reshape(-1),
                    jnp.repeat(logits0, gmax, axis=0), n, rcfg.pad_id)

            with jax.named_scope("engine.prefill"):
                st = jax.lax.cond(refill_mask.any(), do_refill,
                                  lambda s_: dict(s_), st)

            # 3b. resume parked siblings (pure-attention configs): the
            # prompt state is exactly its shared pages (already in the
            # block table) + the saved prompt logits — placement is a
            # pure scatter, nothing recomputes
            rtgt = jnp.where(resume_slots < s_slots, resume_slots,
                             s_slots).astype(jnp.int32)

            def do_resume(st):
                return _place_slot_planes(dict(st), rtgt, resume_lens,
                                          resume_budgets, resume_logits, n,
                                          rcfg.pad_id)

            with jax.named_scope("engine.resume"):
                st = jax.lax.cond(resume_mask.any(), do_resume,
                                  lambda s_: dict(s_), st)

            # 4. masked decode substeps through the block tables
            def substep(st, _):
                st = dict(st)
                with jax.named_scope("engine.sample"):
                    nxt, live = _substep_sample(st, rcfg, n, s_slots)
                # write target: decode token i = n_gen opens/extends the
                # slot's private pages AFTER its prompt pages — never a
                # shared page, so prompt pages stay read-only
                n_pp_s = (st["prompt_len"] + pl_ - 1) // pl_
                page_slot = jnp.minimum(n_pp_s + st["n_gen"] // pl_,
                                        max_pages - 1)
                bt_entry = jnp.take_along_axis(
                    block_tables, page_slot[:, None], axis=1)[:, 0]
                wp = jnp.where(live & (bt_entry >= 0), bt_entry,
                               npg).astype(jnp.int32)
                wo = (st["n_gen"] % pl_).astype(jnp.int32)
                new_logits, new_cache = decode_step(
                    params, cfg, nxt, st["cache"], st["pos"],
                    block_tables=block_tables, write_page=wp, write_off=wo,
                    attn_impl=attn_impl)
                st["cache"] = new_cache
                st = _substep_advance(st, nxt, live, new_logits, rcfg)
                return st, None

            with jax.named_scope("engine.decode"):
                st, _ = jax.lax.scan(substep, st, None,
                                     length=ecfg.steps_per_sync)
            return st

        return paged_engine_step

    # ------------------------------------------------------------- drive
    def _dispatch(self, state, bt, free_mask, refill_toks, refill_lens,
                  refill_prefix_len, refill_prefix_bt, refill_page_ids,
                  refill_slots, refill_budgets, refill_mask, resume_slots,
                  resume_logits, resume_lens, resume_budgets, resume_mask,
                  cancel_mask):
        """Run the round's jitted step over host-built operands and return
        the new device state — the seam the disaggregated engine overrides
        to interpose the cross-slice prefill handoff (DESIGN.md §12)."""
        return self._step(
            self._params, state, jnp.asarray(bt), jnp.asarray(free_mask),
            jnp.asarray(refill_toks), jnp.asarray(refill_lens),
            jnp.asarray(refill_prefix_len), jnp.asarray(refill_prefix_bt),
            jnp.asarray(refill_page_ids), jnp.asarray(refill_slots),
            jnp.asarray(refill_budgets), jnp.asarray(refill_mask),
            jnp.asarray(resume_slots), jnp.asarray(resume_logits),
            jnp.asarray(resume_lens), jnp.asarray(resume_budgets),
            jnp.asarray(resume_mask), jnp.asarray(cancel_mask))

    @property
    def idle(self) -> bool:
        return super().idle and not self._pending

    @property
    def backlog(self) -> int:
        """Queued groups plus partially-placed (parked) groups."""
        return len(self._queue) + len(self._pending)

    def _round(self) -> list:
        """One paged round: harvest (freeing pages), resume parked
        siblings into freed slots, place queued groups with one shared
        prompt prefill each, allocate-ahead decode pages, dispatch the
        jitted step with fresh block tables."""
        if self.chaos is not None:
            # pool-pressure injection point: a PagePoolExhausted raised
            # here is indistinguishable from a real transient exhaustion
            # at placement/allocate-ahead (testing/chaos.py)
            self.chaos.fire("placement", replica=self.chaos_replica,
                            index=self.stats.get("rounds", 0))
        ecfg, rcfg = self.ecfg, self.rcfg
        s_slots, tp = ecfg.num_slots, ecfg.max_prompt_len
        pl_, sps = ecfg.page_len, ecfg.steps_per_sync
        state, slot_uid, queue = self._state, self._slot_uid, self._queue
        harvested, cancel_mask = self._collect_retirements()

        with span("nat.engine.place"):
            if self._prefix_cache is not None:
                # nodes inserted last round are matchable now (their prefill
                # retired with the previous step), and stale-epoch branches
                # whose readers drained get collected
                self._prefix_cache.step()
                self._dirty.update(self._prefix_cache.reap())

            # snapshot prompt logits for parked groups (written by the prefill
            # one round earlier; read before any new prefill reuses the lane)
            if any(rec["logits"] is None for rec in self._pending):
                lane_logits = np.asarray(state["prefill_logits"])
                for rec in self._pending:
                    if rec["logits"] is None:
                        rec["logits"] = lane_logits[rec["lane"]].copy()

            # -- allocate-ahead for slots already decoding: each must own
            # pages for every token it can write this round (exhaustion here
            # is a real undersized pool — raise, never corrupt)
            occupied = [s for s in range(s_slots) if slot_uid[s] is not None]
            for s in occupied:
                want = int(min(self._n_gen_ub[s] + sps, self._slot_budget[s]))
                need = -(-want // pl_)
                short = need - len(self._slot_decode_pages[s])
                if short > 0:
                    self._ensure_free(short)  # evict cold branches, else raise
                while len(self._slot_decode_pages[s]) < need:
                    self._slot_decode_pages[s].extend(
                        self._alloc.alloc(1, f" (slot {s} decode-ahead)"))
            free_slots = [s for s in range(s_slots) if slot_uid[s] is None]

            def place(s: int, r: Request, plen: int, ppages: list,
                      first_ref: bool) -> int:
                """Install sibling ``r`` in slot ``s``: take a prompt-page
                reference (unless it inherits the allocation's first ref) and
                allocate its first decode pages."""
                budget = r.budget or rcfg.max_new_tokens
                if not first_ref:
                    self._alloc.retain(ppages)
                slot_uid[s] = r.uid
                self._streamed[s] = 0
                self._slot_prompt_pages[s] = ppages
                self._slot_decode_pages[s] = self._alloc.alloc(
                    -(-min(sps, budget) // pl_), f" (slot {s} decode)")
                self._slot_plen[s] = plen
                self._slot_budget[s] = budget
                self._n_gen_ub[s] = 0
                occupied.append(s)
                return budget

            # -- resume parked siblings into freed slots (pure scatter: their
            # prompt state is the shared pages + the saved prompt logits);
            # lane width bounds the (lanes, vocab) logits operand per round —
            # leftovers simply wait for the next round
            rw = ecfg.resumes
            resume_mask = np.zeros((rw,), bool)
            resume_slots = np.full((rw,), s_slots, np.int32)
            resume_logits = np.zeros((rw, self.cfg.vocab_size), np.float32)
            resume_lens = np.ones((rw,), np.int32)
            resume_budgets = np.zeros((rw,), np.int32)
            ri = 0
            for rec in list(self._pending):
                still = []
                for r in rec["reqs"]:
                    if r.uid in self._to_cancel:
                        harvested.append(self._cancelled_completion(r))
                    else:
                        still.append(r)
                rec["reqs"] = still
                while (still and free_slots and ri < rw
                       and rec["logits"] is not None):
                    budget = still[0].budget or rcfg.max_new_tokens
                    if not self._ensure_free(-(-min(sps, budget) // pl_)):
                        if not occupied and not resume_mask.any():
                            self._alloc.alloc(  # raises with occupancy
                                -(-min(sps, budget) // pl_),
                                " (sibling resume)")
                        break
                    r = still.pop(0)
                    s = free_slots.pop(0)
                    resume_budgets[ri] = place(s, r, rec["plen"],
                                               rec["ppages"], first_ref=False)
                    resume_mask[ri] = True
                    resume_slots[ri] = s
                    resume_logits[ri] = rec["logits"]
                    resume_lens[ri] = rec["plen"]
                    ri += 1
                if not rec["reqs"]:
                    # last sibling placed/cancelled: drop the record's ref
                    self._dirty.update(self._alloc.release(rec["ppages"]))
                    self._pending.remove(rec)

            # -- place queued groups, one prompt prefill per lane; siblings
            # beyond the free slots are parked (pure-attention) or the whole
            # group waits (per-slot-state mixers place atomically)
            lanes, gmax, n_pp = ecfg.group_lanes, ecfg.max_group, self._n_pp
            refill_mask = np.zeros((lanes,), bool)
            refill_toks = np.full((lanes, tp), rcfg.pad_id, np.int32)
            refill_lens = np.ones((lanes,), np.int32)
            refill_prefix_len = np.zeros((lanes,), np.int32)
            refill_prefix_bt = np.full((lanes, n_pp), -1, np.int32)
            refill_page_ids = np.full((lanes, n_pp), self.num_pages, np.int32)
            refill_slots = np.full((lanes, gmax), s_slots, np.int32)
            refill_budgets = np.zeros((lanes, gmax), np.int32)
            lane = 0
            while lane < lanes and queue and free_slots:
                group = queue[0]
                live = []
                for r in group:
                    if r.uid in self._to_cancel:
                        harvested.append(self._cancelled_completion(r))
                    else:
                        live.append(r)
                # strip emitted cancellations from the QUEUED group in place:
                # the defer breaks below leave the group at the queue head, and
                # a re-examined sibling must never re-emit its Completion
                group[:] = live
                if not live:
                    queue.popleft()
                    continue
                if not self._pure_pool and len(live) > len(free_slots):
                    break  # atomic placement: wait for slots to free up
                placed = live[:len(free_slots)]
                parked = live[len(placed):]
                toks0 = np.asarray(live[0].tokens)
                plen = len(toks0)
                n_pp_g = -(-plen // pl_)
                # radix longest-prefix match: matched pages join the group's
                # block tables read-only; only the suffix prefills.  A fully
                # cached prompt drops its last matched page so >= 1 token is
                # always recomputed — the prefill's last-token logits seed
                # sampling (vLLM-style last-block recompute).
                m_nodes: list = []
                if self._prefix_cache is not None:
                    m_nodes = self._prefix_cache.lookup(toks0)
                    if m_nodes and len(m_nodes) * pl_ >= plen:
                        m_nodes = m_nodes[:-1]
                m_pages = [nd.page for nd in m_nodes]
                mlen = len(m_pages) * pl_
                n_fresh = n_pp_g - len(m_pages)
                need = n_fresh + sum(
                    -(-min(sps, r.budget or rcfg.max_new_tokens) // pl_)
                    for r in placed)
                if m_pages:
                    # pin the match before eviction can consider those pages
                    self._alloc.retain(m_pages)
                    self._prefix_cache.touch(m_nodes)
                if not self._ensure_free(need):
                    if m_pages:
                        self._dirty.update(self._alloc.release(m_pages))
                    if (not occupied and not refill_mask.any()
                            and not resume_mask.any()):
                        self._alloc.alloc(need, " (group placement)")  # raises
                    break  # wait for retirements to return pages
                fresh_pages = self._alloc.alloc(n_fresh, " (group prompt)")
                ppages = m_pages + fresh_pages
                queue.popleft()
                refill_mask[lane] = True
                refill_toks[lane, :plen - mlen] = toks0[mlen:]
                refill_lens[lane] = plen - mlen
                refill_prefix_len[lane] = mlen
                refill_prefix_bt[lane, :len(m_pages)] = m_pages
                refill_page_ids[lane, :n_fresh] = fresh_pages
                for gidx, r in enumerate(placed):
                    s = free_slots.pop(0)
                    refill_slots[lane, gidx] = s
                    refill_budgets[lane, gidx] = place(s, r, plen, ppages,
                                                       first_ref=(gidx == 0))
                if parked:
                    self._alloc.retain(ppages)  # the pending record's ref
                    self._pending.append({"reqs": parked, "ppages": ppages,
                                          "plen": plen, "lane": lane,
                                          "logits": None})
                if self._prefix_cache is not None:
                    # chain the suffix's FULL pages into the trie (ready next
                    # round, once their prefill has retired); the partial
                    # trailing page stays group-private
                    n_full_new = plen // pl_ - len(m_pages)
                    if n_full_new > 0:
                        self._prefix_cache.insert(
                            m_nodes[-1] if m_nodes else None, toks0, mlen,
                            fresh_pages[:n_full_new])
                    self.stats["prefix_hit_tokens"] += mlen
                self.stats["prompt_tokens"] += plen
                self.stats["prefill_tokens"] += plen - mlen
                self.stats["prompt_prefills"] += 1
                lane += 1

            if (not refill_mask.any() and not resume_mask.any()
                    and not occupied):
                self.last_state = state  # session quiescent: expose for tests
                return harvested

            # -- block tables + free-page invalidation mask, rebuilt per round
            bt = np.full((s_slots, self._max_pages), -1, np.int32)
            for s in occupied:
                n_pp_s = -(-int(self._slot_plen[s]) // pl_)
                bt[s, :n_pp_s] = self._slot_prompt_pages[s]
                dp = self._slot_decode_pages[s]
                bt[s, n_pp_s:n_pp_s + len(dp)] = dp
            free_mask = np.zeros((self.num_pages,), bool)
            if self._dirty:
                free_mask[sorted(self._dirty)] = True

        with span("nat.engine.dispatch"):
            self._state = self._dispatch(
                state, bt, free_mask, refill_toks, refill_lens,
                refill_prefix_len, refill_prefix_bt, refill_page_ids,
                refill_slots, refill_budgets, refill_mask, resume_slots,
                resume_logits, resume_lens, resume_budgets, resume_mask,
                cancel_mask)
        self._dirty.clear()
        for s in occupied:
            self._n_gen_ub[s] = min(self._n_gen_ub[s] + sps,
                                    int(self._slot_budget[s]))
        self.stats["rounds"] += 1
        self.stats["decode_steps"] += sps
        self.stats["slot_substeps"] += sps * s_slots
        self.stats["refills"] += (int((refill_slots < s_slots).sum())
                                  + int(resume_mask.sum()))
        self.stats["pages_in_use"] = self._alloc.in_use
        self.stats["peak_pages_in_use"] = self._alloc.peak_in_use
        return harvested


class DisaggPagedRolloutEngine(PagedRolloutEngine):
    """Prefill/decode-disaggregated paged engine (DESIGN.md §12).

    The paged round's one fused step does both prompt prefill and decode
    substeps on one device; this engine splits them across a fleet slice's
    two cells: prompt prefill runs as its own jitted cell on the
    **prefill device**, and its output — the prompt logits plus the fresh
    per-layer page payloads — is shipped device-to-device to the **decode
    device**, where the (external-prefill) step scatters it into the
    shared pool exactly as the fused step would have.  The handoff is the
    group's block-table contract: pages are allocated on the decode slice
    by the same host allocator, prefill writes arrive via the existing
    scatter path, and nothing else (counters, planes, block tables)
    changes — so token streams are bit-identical to the fused engine.

    Requires every mixer pool-resident (``capabilities.check_slice_handoff``):
    per-slot sequence state (local rings, ssm/rec) would be stranded on
    the prefill slice.  The radix prefix cache is incompatible — a match
    would need decode-slice pool pages inside the prefill computation.
    """

    def __init__(self, cfg: ModelConfig, rcfg, ecfg: PagedEngineConfig,
                 *, prefill_device=None, decode_device=None):
        caps.check_slice_handoff(cfg)
        if ecfg.prefix_cache:
            raise ValueError(
                "prefix_cache cannot span the prefill/decode split: the "
                "radix match needs decode-slice pool pages inside the "
                "prefill computation")
        self._prefill_device = prefill_device or jax.devices()[0]
        super().__init__(cfg, rcfg, ecfg,
                         device=decode_device or jax.devices()[0])
        self._prefill_fn = jax.jit(self._make_prefill())
        self._params_prefill = None
        self._zero_handoff = None

    def _make_step(self, external_prefill: bool = True):
        return super()._make_step(external_prefill=True)

    def _make_prefill(self):
        cfg, cache_len = self.cfg, self.cache_len

        def prefill_cell(params, toks, lens):
            # the exact computation the fused step's do_refill runs (prefix
            # cache off), so the handoff changes placement, never values
            return paged_prefill(params, cfg, toks, cache_len=cache_len,
                                 prefill_len=jnp.maximum(lens, 1),
                                 prefix_kv=None, prefix_len=None)

        return prefill_cell

    def begin(self, params, key: Array, *, on_finish=None,
              on_token=None) -> None:
        # handoff counters are cumulative across group sessions (the
        # trainer's publication_stats reads them as lifetime telemetry);
        # the parent resets self.stats per session, so carry them over
        carry = {k: getattr(self, "stats", {}).get(k, 0)
                 for k in ("handoffs", "handoff_bytes")}
        super().begin(params, key, on_finish=on_finish, on_token=on_token)
        self.stats.update(carry)
        self._params_prefill = jax.device_put(params, self._prefill_device)
        if self._zero_handoff is None:
            lanes, tp = self.ecfg.group_lanes, self.ecfg.max_prompt_len
            shapes = jax.eval_shape(
                self._prefill_fn, self._params_prefill,
                jnp.zeros((lanes, tp), jnp.int32),
                jnp.ones((lanes,), jnp.int32))
            # pure-decode rounds still pass handoff operands (static jit
            # signature); zero-filled once, resident on the decode slice
            self._zero_handoff = jax.device_put(
                jax.tree.map(lambda d: jnp.zeros(d.shape, d.dtype), shapes),
                self._device)

    def set_params(self, params) -> None:
        super().set_params(params)
        self._params_prefill = jax.device_put(params, self._prefill_device)

    def _dispatch(self, state, bt, free_mask, refill_toks, refill_lens,
                  refill_prefix_len, refill_prefix_bt, refill_page_ids,
                  refill_slots, refill_budgets, refill_mask, resume_slots,
                  resume_logits, resume_lens, resume_budgets, resume_mask,
                  cancel_mask):
        if refill_mask.any():
            toks = jax.device_put(jnp.asarray(refill_toks),
                                  self._prefill_device)
            lens = jax.device_put(jnp.asarray(refill_lens),
                                  self._prefill_device)
            logits0, fresh = self._prefill_fn(
                self._params_prefill, toks, lens)
            handoff = jax.device_put((logits0, fresh), self._device)
            self.stats["handoffs"] += 1
            self.stats["handoff_bytes"] += _tree_bytes(handoff)
        else:
            handoff = self._zero_handoff
        return self._step(
            self._params, state, jnp.asarray(bt), jnp.asarray(free_mask),
            jnp.asarray(refill_toks), jnp.asarray(refill_lens),
            jnp.asarray(refill_prefix_len), jnp.asarray(refill_prefix_bt),
            jnp.asarray(refill_page_ids), jnp.asarray(refill_slots),
            jnp.asarray(refill_budgets), jnp.asarray(refill_mask),
            jnp.asarray(resume_slots), jnp.asarray(resume_logits),
            jnp.asarray(resume_lens), jnp.asarray(resume_budgets),
            jnp.asarray(resume_mask), jnp.asarray(cancel_mask), *handoff)


def make_paged_engine(cfg: ModelConfig, rcfg, *, num_slots: int,
                      max_prompt_len: int, steps_per_sync: int = 4,
                      page_len: int = 16, num_pages: int = 0,
                      max_group: int = 0, attn_impl: str = "ref",
                      prefix_cache: bool = False,
                      learner_retain: bool = False,
                      ) -> PagedRolloutEngine:
    return PagedRolloutEngine(
        cfg, rcfg, PagedEngineConfig(
            num_slots=num_slots, max_prompt_len=max_prompt_len,
            steps_per_sync=steps_per_sync, page_len=page_len,
            num_pages=num_pages,
            max_group=max_group or min(num_slots, rcfg.group_size),
            attn_impl=attn_impl, prefix_cache=prefix_cache,
            learner_retain=learner_retain))
