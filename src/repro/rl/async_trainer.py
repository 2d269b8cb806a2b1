"""Stream-overlapped NAT trainer: bounded-staleness actor/learner pipeline.

The serial trainer pays a serial tax NAT's own systems analysis warns
about: the learner idles while long-tail rollouts drain, and the slot
arena idles during backprop.  This module splits the step into two loops
connected by a bounded-staleness sample queue (DESIGN.md §6):

* **Actor** (background thread) — drives the rollout engine, one *group*
  (= P prompts x G kept rollouts, over-provisioned and quota-cancelled) at
  a time, tagging each with the policy version that generated it, and
  deposits assembled groups into the queue.  With ``max_staleness > 0``
  the actor streams groups through a persistent engine session, so a new
  group's prompts refill slots freed by the previous group's stragglers —
  the arena never drains to a barrier between steps.
* **Learner** (the caller of ``train_step``) — pops the oldest group,
  scores rewards, draws the NAT selection, and applies the HT-weighted
  GRPO update.  Samples whose behaviour version lags the learner get a
  truncated importance correction composed with their HT weights
  (``core/grpo.py::nat_grpo_loss``); the queue refuses to serve anything
  staler than ``max_staleness`` versions.

Weight publication is a versioned snapshot swap: the learner rebinds a
``(params, version)`` tuple; the actor picks it up at its next group
admission and hands it to the engine via ``set_params`` — the jitted
engine step in flight keeps the (immutable) reference it was called with,
so publication never copies or races device work.

``max_staleness=0`` degenerates to the serial trainer *token-exactly* —
and structurally: no actor thread exists at all (a thread could only roll
while ``train_step`` blocked on it, so it would be pure overhead and a
leak for callers that never ``close()``); the group is produced inline on
a per-group engine session with the same key chain, and the staleness
correction multiplies by exactly 1.0 (``tests/test_async_trainer.py``
asserts bitwise parity).  ``rl/trainer.py::NATGRPOTrainer`` is that
special case, kept as the stable serial entry point.  ``max_staleness>0``
trainers own a daemon actor thread: call ``close()`` when done with one.
"""
from __future__ import annotations

import bisect
import dataclasses
import threading
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grpo import GRPOConfig, group_advantages
from repro.core.layout import make_layout
from repro.core.repack import bucket_ladder
from repro.core.selectors import EntropySelector, make_selector
from repro.models import capabilities as caps
# NOTE: repro.data sits ABOVE repro.rl in the layering (data imports
# rl.env), so importing it at module scope would be circular whenever
# repro.data.pipeline is the entry point.  Import lazily at use sites.
from repro.models.config import ModelConfig
from repro.models.params import init_params
from repro.models.model import model_decl
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.rl.env import make_env
from repro.rl.learner import make_train_step
from repro.rl.rollout import (
    RolloutConfig, batch_from_completions, rollout_group,
    rollout_group_continuous,
)

span = jax.profiler.TraceAnnotation    # host span in the profiler's trace


@dataclasses.dataclass(frozen=True)
class NATTrainerConfig:
    env: str = "mod_arith"
    env_kwargs: tuple = ()
    selector: str = "rpc"            # full | urs | rpc | det_trunc | entropy
    selector_kwargs: tuple = ()      # e.g. (("min_cut", 8),) or (("p", 0.5),)
    prompts_per_step: int = 8        # P
    max_prompt_len: int = 24
    rollout: RolloutConfig = RolloutConfig()
    # continuous (dense slot arena) | paged (paged KV pool with group
    # prefix sharing, DESIGN.md §8) | legacy (fixed-shape scan)
    rollout_engine: str = "continuous"
    num_slots: int = 0               # arena slots; 0 -> P * G
    steps_per_sync: int = 4          # engine decode substeps per host sync
    page_len: int = 16               # paged arena: tokens per KV page
    num_pages: int = 0               # paged arena: pool size; 0 -> worst case
    grpo: GRPOConfig = GRPOConfig()
    adamw: AdamWConfig = AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=500)
    bucket_align: int = 16
    num_buckets: int = 4
    repack: bool = True              # physical prefix truncation for RPC
    # batch layout for the learner step (core/layout.py, DESIGN.md §7):
    # "" derives from ``repack`` ("bucketed" when True, "padded" otherwise);
    # "packed" bin-packs kept-span hulls into dense segment-id rows
    layout: str = ""
    layout_kwargs: tuple = ()        # e.g. (("row_quant", 2),)
    seed: int = 0
    # -- actor/learner overlap (DESIGN.md §6) --
    max_staleness: int = 0           # 0 reproduces the serial trainer exactly
    queue_groups: int = 0            # sample-queue capacity; 0 -> staleness+1
    # -- disaggregated fleets (DESIGN.md §12, rl/dist_trainer.py) --
    fleet: int = 0                   # N>0: N replicated rollout fleet slices
    disagg: str = ""                 # "" | "prefill,decode": split each slice
    # -- supervision / elasticity (DESIGN.md §13, rl/supervision.py) --
    supervise: bool = True           # heartbeat + reclaim supervisor (fleets)
    hang_timeout: float = 300.0      # claimed group + no heartbeat/progress
    supervise_interval: float = 0.2  # monitor poll period
    publish_retries: int = 3         # bounded WeightPublisher attempts
    publish_backoff: float = 0.05    # base publish backoff (doubles/attempt)
    placement_retries: int = 3       # bounded attempts under PagePoolExhausted
    placement_backoff: float = 0.05  # base placement backoff (doubles/attempt)


@dataclasses.dataclass
class TaggedGroup:
    """One finished rollout group in the sample queue."""

    index: int             # actor step index (== the learner step it feeds)
    behavior_version: int  # learner version whose params generated it
    batch: object          # RolloutBatch
    prompt_batch: object   # data.pipeline.PromptBatch (for reward eval)
    key_sel: jax.Array     # the selection key split for this step
    t_rollout: float       # actor wall-clock spent rolling the group
    # actor key-chain state *before* this group's splits: checkpoints rewind
    # to the oldest unconsumed group so resume re-rolls it identically
    key0: Optional[jax.Array] = None


class StaleSampleError(RuntimeError):
    """A queued group exceeded the staleness bound (never served)."""


class SampleQueue:
    """Bounded, index-ordered queue between actor(s) and learner with a
    staleness contract: ``pop(current_version)`` never returns a group whose
    behaviour version lags by more than ``max_staleness`` — over-stale groups
    are dropped and counted, not served.  Errors from a producing thread
    surface on the consumer via ``fail`` (first error wins: a later ``fail``
    — e.g. the poison pill from ``close()`` — never masks the root cause).

    **Multi-producer ordering (DESIGN.md §12).**  With one actor, groups
    arrive already index-ordered and this is the PR 3 FIFO.  With a fleet of
    N actors racing, groups finish out of order; the learner still consumes
    the serial index sequence, so the queue reassembles: ``put`` inserts
    sorted by ``TaggedGroup.index``, and a producer **reserves** its index
    before rolling so ``pop`` can tell "index 4 is absent" from "index 4 is
    still in flight" and hold younger groups until the gap fills.  A
    reservation counts toward capacity (the slot is pre-admitted), which is
    what makes reassembly deadlock-free: the deposit of a reserved group
    never blocks on a full queue, so the oldest in-flight group can always
    land and unblock the head.  ``watermarks`` tracks, per producer, the
    newest behaviour version deposited — the fleet's publication-lag
    telemetry."""

    def __init__(self, capacity: int, max_staleness: int):
        self.capacity = max(1, capacity)
        self.max_staleness = max_staleness
        self.dropped_stale = 0
        self.dropped_dup = 0             # late re-deposits of a served index
        self.watermarks: Dict[str, int] = {}
        # fault-injection hook (testing/chaos.py, DESIGN.md §13): when set,
        # fired at put() entry with the producer name and group index
        self.chaos = None
        self._items: list = []           # sorted by .index (stable)
        self._keys: list = []            # parallel list of .index
        self._inflight: set = set()      # reserved, not yet deposited
        self._max_served = -1            # newest index pop() has returned
        self._cv = threading.Condition()
        self._error: Optional[BaseException] = None

    def qsize(self) -> int:
        with self._cv:
            return len(self._items)

    def inflight(self) -> int:
        with self._cv:
            return len(self._inflight)

    def peek(self) -> Optional[TaggedGroup]:
        """The oldest queued group without consuming it (None when empty)."""
        with self._cv:
            return self._items[0] if self._items else None

    def fail(self, err: BaseException) -> None:
        with self._cv:
            if self._error is None:  # first error wins
                self._error = err
            self._cv.notify_all()

    def reserve(self, index: int, timeout: Optional[float] = None) -> None:
        """Claim ``index`` before rolling it.  Blocks while the queue plus
        in-flight reservations are at capacity, so total admitted work is
        bounded; the matching ``put`` is then exempt from the capacity
        wait.  Pair with ``cancel`` on abandonment."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while (len(self._items) + len(self._inflight) >= self.capacity
                   and self._error is None):
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("SampleQueue.reserve timed out")
                self._cv.wait(0.05)
            if self._error is not None:
                raise self._error
            self._inflight.add(index)

    def cancel(self, index: int) -> None:
        """Drop a reservation without depositing (producer abandoned the
        group); ``pop`` stops waiting for the gap."""
        with self._cv:
            self._inflight.discard(index)
            self._cv.notify_all()

    def remove_producer(self, name: str, *, cancel: tuple = ()) -> None:
        """Forget a dead producer (supervision, DESIGN.md §13): its
        watermark is deleted so publication-lag telemetry never reports a
        ghost, and any reservation indices in ``cancel`` that nobody will
        reclaim are dropped so ``pop`` stops holding younger groups for
        them.  (The supervisor's reclaim path instead *keeps* the dead
        replica's reservation — a survivor adopts it and deposits under
        the same index.)"""
        with self._cv:
            self.watermarks.pop(name, None)
            for i in cancel:
                self._inflight.discard(i)
            self._cv.notify_all()

    def put(self, group: TaggedGroup, timeout: Optional[float] = None,
            producer: Optional[str] = None) -> None:
        if self.chaos is not None:
            self.chaos.fire("queue_put", replica=producer,
                            index=group.index)
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            if (group.index <= self._max_served
                    or group.index in self._keys):
                # late duplicate: a condemned replica woke up after its
                # claimed group was reclaimed and re-deposited (or even
                # already consumed).  At-most-once per index: drop it,
                # release any stale reservation, count it.
                self._inflight.discard(group.index)
                self.dropped_dup += 1
                self._cv.notify_all()
                return
            while (group.index not in self._inflight
                   and len(self._items) >= self.capacity
                   and self._error is None):
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("SampleQueue.put timed out")
                self._cv.wait(0.05)
            if self._error is not None:
                raise self._error
            self._inflight.discard(group.index)
            if producer is not None:
                self.watermarks[producer] = max(
                    self.watermarks.get(producer, -1), group.behavior_version)
            k = bisect.bisect_right(self._keys, group.index)
            self._keys.insert(k, group.index)
            self._items.insert(k, group)
            self._cv.notify_all()

    def _head_ready(self) -> bool:
        """Serve the head only when no smaller index is still in flight —
        the learner consumes the serial index order."""
        if not self._items:
            return False
        return not self._inflight or self._keys[0] < min(self._inflight)

    def pop(self, current_version: int,
            timeout: Optional[float] = None) -> TaggedGroup:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while True:
                if self._error is not None:
                    raise self._error
                while self._head_ready():
                    g = self._items.pop(0)
                    self._keys.pop(0)
                    self._max_served = max(self._max_served, g.index)
                    self._cv.notify_all()  # wake a producer blocked on full
                    if (current_version - g.behavior_version
                            <= self.max_staleness):
                        return g
                    # the staleness contract: drop, never serve
                    self.dropped_stale += 1
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError("SampleQueue.pop timed out")
                self._cv.wait(0.05)


class KeyChain:
    """Thread-safe view of the actor's serial key chain (DESIGN.md §12).

    The serial trainer derives group ``i``'s keys by walking
    ``state, k_roll, k_sel = split(state, 3)`` from the seed.  A fleet of
    actors claims indices out of order, so the chain is materialized lazily
    and cached: ``keys_for(i)`` returns the exact ``(key0, k_roll, k_sel)``
    the serial walk would produce for group ``i``, whichever replica asks
    first.  This is what makes fleet rollouts per-group token-exact against
    the single-engine oracle — same index, same keys, same tokens."""

    def __init__(self, key0: jax.Array, base_index: int = 0):
        self._lock = threading.Lock()
        self._base = base_index
        self._states = [key0]    # _states[k] = chain state before base+k

    def _state(self, k: int) -> jax.Array:
        if k < 0:
            raise IndexError(f"group index below chain base {self._base}")
        while len(self._states) <= k:
            self._states.append(jax.random.split(self._states[-1], 3)[0])
        return self._states[k]

    def state_before(self, i: int) -> jax.Array:
        """Chain state before group ``i``'s splits (checkpoint rewind)."""
        with self._lock:
            return self._state(i - self._base)

    def keys_for(self, i: int):
        """``(key0, k_roll, k_sel)`` for group ``i`` — the serial walk's
        exact splits, regardless of claim order."""
        with self._lock:
            key0 = self._state(i - self._base)
            _, k_roll, k_sel = jax.random.split(key0, 3)
            return key0, k_roll, k_sel


class _GroupState:
    """Actor-side assembly buffer for one in-flight streaming group."""

    def __init__(self, index, pb, key_sel, version, p, g, gp, budget_total,
                 stats0, key0=None):
        self.index = index
        self.pb = pb
        self.key_sel = key_sel
        self.version = version
        self.key0 = key0
        self.comps: dict = {}            # local row -> Completion
        self.n_completed = np.zeros((p,), np.int32)
        self.g, self.gp = g, gp
        self.budget_total = budget_total
        self.stats0 = stats0             # engine cumulative stats at admission
        self.t_admit = time.perf_counter()


class AsyncNATGRPOTrainer:
    """The full NAT-GRPO loop with bounded-staleness actor/learner overlap.

    ``budget_fn(step, row) -> int`` optionally overrides the decode budget
    per rollout row (row = prompt_index * G' + j); benches use it to shape
    straggler mixes, schedules can use it as a length curriculum.
    """

    def __init__(self, model_cfg: ModelConfig, tcfg: NATTrainerConfig,
                 params=None, mesh=None, rules=None,
                 budget_fn: Optional[Callable[[int, int], int]] = None,
                 chaos=None):
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.budget_fn = budget_fn
        # fault-injection plan (testing/chaos.py, DESIGN.md §13) threaded
        # into the queue/engine hook points; None in production
        self.chaos = chaos
        self.env = make_env(tcfg.env, **dict(tcfg.env_kwargs))
        from repro.data.pipeline import PromptPipeline

        self.pipeline = PromptPipeline(
            self.env, batch_size=tcfg.prompts_per_step,
            max_prompt_len=tcfg.max_prompt_len, seed=tcfg.seed)
        key = jax.random.PRNGKey(tcfg.seed)
        if params is None:
            key, k = jax.random.split(key)
            params = init_params(k, model_decl(model_cfg))
        # the actor owns the serial trainer's key chain (token-exact parity
        # at max_staleness=0); evaluate() gets its own decorrelated stream
        self._actor_key = key
        self.key = jax.random.fold_in(key, 0xE7A1)
        self.params = params
        self.opt_state = init_opt_state(params, tcfg.adamw)
        self.selector = make_selector(tcfg.selector, **dict(tcfg.selector_kwargs))
        if tcfg.rollout_engine not in ("continuous", "paged", "legacy"):
            raise ValueError(f"unknown rollout_engine {tcfg.rollout_engine!r}")
        self.engine = self._build_engine()
        self.step_count = 0
        layout_name = tcfg.layout or ("bucketed" if tcfg.repack else "padded")
        if layout_name == "packed":
            # fail at config time, naming the capability-table row, rather
            # than silently falling back or erroring steps later in-jit
            caps.check_packed(model_cfg)
        if layout_name == "paged":
            # the paged layout needs the page handoff from a learner-retain
            # rollout session (export_learner_pages), which this trainer's
            # replay/checkpoint contract does not carry yet — drive it via
            # rl.learner.make_train_step(paged=True) directly (DESIGN.md §11)
            raise NotImplementedError(
                "NATTrainerConfig(layout='paged') is not wired into the "
                "async trainer; use core.layout.PagedLayout + "
                "rl.learner.make_train_step(paged=True) with a "
                "learner_retain paged engine (DESIGN.md §11)")
        self.layout = make_layout(layout_name, **dict(tcfg.layout_kwargs))
        # the optimizer state is donated: old and new f32 moments never
        # coexist, which is what lets a one-chip share of an 8B-width model
        # fit 16 GB.  Params are not: the published snapshot aliases them.
        self._train_step = jax.jit(make_train_step(
            model_cfg, tcfg.grpo, tcfg.adamw, mesh=mesh, rules=rules,
            vocab_chunks=1, packed=self.layout.packed), donate_argnums=(1,))
        t_max = tcfg.max_prompt_len + tcfg.rollout.max_new_tokens
        self.ladder = bucket_ladder(t_max, tcfg.num_buckets, tcfg.bucket_align)
        self.history: list = []

        # -- actor/learner machinery --
        p, g = tcfg.prompts_per_step, tcfg.rollout.group_size
        self._p, self._g = p, g
        self._gp = int(np.ceil(g * tcfg.rollout.overprovision))
        self._rows = p * self._gp
        # capacity floor of max_staleness+1 guarantees the deposit of every
        # admitted group fits, so the actor can never wedge in put() while
        # a checkpoint quiesce waits for it
        self.queue = SampleQueue(
            max(tcfg.queue_groups or 0, tcfg.max_staleness + 1),
            tcfg.max_staleness)
        self.queue.chaos = chaos
        if chaos is not None and self.engine is not None:
            self.engine.chaos = chaos
        self._cv = threading.Condition()
        self._learner_version = 0
        self._next_group = 0
        self._published = (self.params, 0)   # versioned snapshot
        self._paused = False
        self._stop_evt = threading.Event()
        self._actor_idle = threading.Event()
        self._actor: Optional[threading.Thread] = None
        self._stream_groups: dict = {}

    def _build_engine(self, *, device=None, prefill_device=None):
        """Construct one rollout engine per the config — the seam the
        disaggregated trainer reuses to build slice-pinned fleet replicas
        (``device`` commits the arena; ``prefill_device`` additionally
        splits prompt prefill onto its own cell, DESIGN.md §12).  Returns
        None for the legacy scan / codebook models (no arena)."""
        tcfg, model_cfg = self.tcfg, self.model_cfg
        if tcfg.rollout_engine == "paged" and not model_cfg.num_codebooks:
            from repro.rl.engine import (
                DisaggPagedRolloutEngine, PagedEngineConfig,
                PagedRolloutEngine,
            )

            gp = int(np.ceil(tcfg.rollout.group_size
                             * tcfg.rollout.overprovision))
            # default slot count must cover one full G' group: configs
            # with per-slot sequence state place groups atomically
            pecfg = PagedEngineConfig(
                num_slots=tcfg.num_slots
                or max(tcfg.prompts_per_step * tcfg.rollout.group_size, gp),
                max_prompt_len=tcfg.max_prompt_len,
                steps_per_sync=tcfg.steps_per_sync,
                page_len=tcfg.page_len, num_pages=tcfg.num_pages,
                max_group=gp)
            if prefill_device is not None:
                return DisaggPagedRolloutEngine(
                    model_cfg, tcfg.rollout, pecfg,
                    prefill_device=prefill_device, decode_device=device)
            return PagedRolloutEngine(model_cfg, tcfg.rollout, pecfg,
                                      device=device)
        elif (tcfg.rollout_engine == "continuous"
              and not model_cfg.num_codebooks):
            from repro.rl.engine import ContinuousRolloutEngine, EngineConfig

            return ContinuousRolloutEngine(
                model_cfg, tcfg.rollout, EngineConfig(
                    num_slots=tcfg.num_slots
                    or tcfg.prompts_per_step * tcfg.rollout.group_size,
                    max_prompt_len=tcfg.max_prompt_len,
                    steps_per_sync=tcfg.steps_per_sync),
                device=device)
        # legacy scan — explicit opt-out, or codebook models (audio),
        # which the slot arena does not serve yet
        return None

    # ------------------------------------------------------------- actor side
    def _ensure_actor(self) -> None:
        """Start the actor thread — only for ``max_staleness > 0``.  At
        staleness 0 the learner gate makes a thread pure overhead (it could
        only roll while a ``train_step`` is blocked waiting for it), so the
        serial path produces groups inline and owns no thread at all:
        nothing leaks when callers never ``close()``."""
        if self.tcfg.max_staleness == 0:
            return
        if self._actor is None or not self._actor.is_alive():
            self._stop_evt.clear()
            target = (self._actor_streaming if self.engine is not None
                      else self._actor_pergroup)
            self._actor = threading.Thread(
                target=self._actor_main, args=(target,), daemon=True,
                name="nat-actor")
            self._actor.start()

    def _actor_main(self, target) -> None:
        try:
            target()
        except BaseException as e:  # surface on the learner thread
            self.queue.fail(e)

    def _gate_open(self, i: int) -> bool:
        return i - self._learner_version <= self.tcfg.max_staleness

    def _budgets_for(self, step: int) -> Optional[np.ndarray]:
        if self.budget_fn is None:
            return None
        n = self.tcfg.rollout.max_new_tokens
        return np.array(
            [min(n, max(1, int(self.budget_fn(step, r))))
             for r in range(self._rows)], np.int32)

    def _roll_next_group(self, params, version: int) -> TaggedGroup:
        """Roll group ``self._next_group`` to completion on a per-group
        engine session — the serial trainer's exact computation — and
        advance the cursor.  Called inline by the staleness-0 learner and
        from the actor thread for the legacy-rollout overlap path."""
        tcfg = self.tcfg
        i = self._next_group
        pb = self.pipeline.batch_at(i)
        self.pipeline.step = i + 1  # keep the checkpoint cursor honest
        key0 = self._actor_key
        self._actor_key, k_roll, k_sel = jax.random.split(self._actor_key, 3)
        with span("nat.rollout"):
            t0 = time.perf_counter()
            if self.engine is not None:
                rb = rollout_group_continuous(
                    params, self.model_cfg, tcfg.rollout,
                    pb.tokens, pb.prompt_lens, k_roll, engine=self.engine,
                    budgets=self._budgets_for(i))
            else:
                rb = rollout_group(params, self.model_cfg, tcfg.rollout,
                                   pb.tokens, pb.prompt_lens, k_roll)
            t_rollout = time.perf_counter() - t0
        self._next_group = i + 1
        return TaggedGroup(
            index=i, behavior_version=version, batch=rb,
            prompt_batch=pb, key_sel=k_sel, t_rollout=t_rollout, key0=key0)

    def _actor_pergroup(self) -> None:
        """Per-group rollouts from a pipelined thread: the overlap path for
        the legacy scan rollout (no arena to stream through)."""
        while not self._stop_evt.is_set():
            with self._cv:
                while (not self._stop_evt.is_set()
                       and (self._paused
                            or not self._gate_open(self._next_group))):
                    self._actor_idle.set()
                    self._cv.wait(0.05)
                if self._stop_evt.is_set():
                    return
                # clear under the lock: _quiesce must never observe an idle
                # flag left over from the gate wait while a roll is starting
                self._actor_idle.clear()
                params, version = self._published
            self.queue.put(self._roll_next_group(params, version))

    # -- streaming mode: persistent session, groups drain across boundaries
    def _admit_group(self) -> bool:
        from repro.rl.engine import Request

        with self._cv:
            if self._paused or not self._gate_open(self._next_group):
                return False
            params, version = self._published
        i = self._next_group
        pb = self.pipeline.batch_at(i)
        self.pipeline.step = i + 1
        key0 = self._actor_key
        # same chain layout as the per-group path (k_roll feeds the session
        # at begin(); per-admission it is split but unused)
        self._actor_key, _k_roll, k_sel = jax.random.split(self._actor_key, 3)
        self.engine.set_params(params)  # snapshot swap at a round boundary
        budgets = self._budgets_for(i)
        n = self.tcfg.rollout.max_new_tokens
        gs = _GroupState(
            i, pb, k_sel, version, self._p, self._g, self._gp,
            int(budgets.sum()) if budgets is not None else self._rows * n,
            dict(self.engine.stats), key0=key0)
        self._stream_groups[i] = gs
        # group-wise submission: the paged arena prefills each prompt once
        # and shares its pages across the G' siblings; on the dense arena
        # submit_group is plain FIFO submit, so the stream is unchanged
        for pi in range(self._p):
            self.engine.submit_group([
                Request(
                    uid=i * self._rows + pi * self._gp + j,
                    tokens=np.asarray(pb.tokens[pi, :int(pb.prompt_lens[pi])]),
                    budget=(int(budgets[pi * self._gp + j])
                            if budgets is not None else n))
                for j in range(self._gp)])
        self._next_group = i + 1
        return True

    def _stream_on_finish(self, c):
        """Quota cancellation, routed per group: the moment a prompt has G
        completed rollouts, its unfinished siblings are cancelled."""
        gi, local = divmod(c.uid, self._rows)
        gs = self._stream_groups[gi]
        gs.comps[local] = c
        pi = local // self._gp
        if not c.completed:
            return None
        gs.n_completed[pi] += 1
        if gs.n_completed[pi] == self._g:
            base = gi * self._rows + pi * self._gp
            return [base + j for j in range(self._gp)
                    if pi * self._gp + j not in gs.comps]
        return None

    def _assemble_ready(self) -> int:
        """Deposit every fully-harvested streaming group, oldest first."""
        deposited = 0
        for gi in sorted(self._stream_groups):
            gs = self._stream_groups[gi]
            if len(gs.comps) < self._rows:
                break  # FIFO: group gi blocks younger groups
            comps = [gs.comps[l] for l in range(self._rows)]
            cur = self.engine.stats
            stats = {
                "tokens_generated": int(sum(c.response_len for c in comps)),
                "cancelled": int(sum(c.cancelled for c in comps)),
                "tokens_budget": gs.budget_total,
                # engine-wide deltas since admission: an *attribution* of
                # shared arena work, exact only when groups do not overlap
                "rounds": cur["rounds"] - gs.stats0["rounds"],
                "decode_steps": cur["decode_steps"] - gs.stats0["decode_steps"],
                "slot_substeps": (cur["slot_substeps"]
                                  - gs.stats0["slot_substeps"]),
                "refills": cur["refills"] - gs.stats0["refills"],
                "sync_s": cur["sync_s"] - gs.stats0["sync_s"],
                "host_s": cur["host_s"] - gs.stats0["host_s"],
            }
            rb = batch_from_completions(
                comps, gs.pb.tokens, gs.pb.prompt_lens, self.tcfg.rollout,
                self._p, self._g, self._gp, stats)
            del self._stream_groups[gi]
            self.queue.put(TaggedGroup(
                index=gi, behavior_version=gs.version, batch=rb,
                prompt_batch=gs.pb, key_sel=gs.key_sel,
                t_rollout=time.perf_counter() - gs.t_admit, key0=gs.key0))
            deposited += 1
        return deposited

    def _actor_streaming(self) -> None:
        k_session = jax.random.fold_in(self._actor_key, 0x5e55)
        self.engine.begin(self._published[0], k_session,
                          on_finish=self._stream_on_finish)
        while not self._stop_evt.is_set():
            admitted = self._admit_group()
            progressed = False
            if not self.engine.idle:
                self.engine.drive()  # on_finish routes into _stream_groups
                progressed = True
            if self._assemble_ready():
                progressed = True
            if not (admitted or progressed):
                with self._cv:
                    self._actor_idle.set()
                    self._cv.wait(0.05)
                self._actor_idle.clear()

    # ----------------------------------------------------------- learner side
    def _publish(self) -> None:
        with self._cv:
            self._learner_version += 1
            self._published = (self.params, self._learner_version)
            self._cv.notify_all()

    def train_step(self) -> dict:
        """One learner step; traced as the profiler span ``nat.train_step``
        holding ``nat.rollout``, ``nat.select``, ``nat.layout`` and
        ``nat.learn`` (``nat.learn.dispatch``, ``nat.learn.sync``,
        ``nat.publish``), the last covering ``time_learn``'s interval."""
        with span("nat.train_step"):
            return self._step()

    def _step(self) -> dict:
        self._ensure_actor()
        t0 = time.perf_counter()
        tcfg = self.tcfg
        # generous pop timeout: surfaces a wedged actor as an error instead
        # of a hung CI job (actor errors propagate via SampleQueue.fail)
        if tcfg.max_staleness == 0 and self.queue.qsize() == 0:
            # serial path: produce inline, no actor thread exists (the gate
            # would only ever let it roll while this call waited anyway)
            with self._cv:
                params, version = self._published
            self.queue.put(self._roll_next_group(params, version))
            tg = self.queue.pop(self._learner_version, timeout=600.0)
        else:
            with span("nat.rollout"):    # the wait for the actor's group
                tg = self.queue.pop(self._learner_version, timeout=600.0)
        rb, pb = tg.batch, tg.prompt_batch
        staleness = self._learner_version - tg.behavior_version
        t_roll = time.perf_counter()

        with span("nat.select"):
            # rewards on FULL responses (never affected by token selection)
            p, g = tcfg.prompts_per_step, tcfg.rollout.group_size
            rewards = np.zeros((p, g), np.float32)
            for i in range(p):
                for j in range(g):
                    r = i * g + j
                    pl, rl = int(rb.prompt_lens[r]), int(rb.response_lens[r])
                    resp = rb.tokens[r, pl:pl + rl]
                    rewards[i, j] = self.env.reward(pb.prompts[i], resp)
            adv = np.asarray(group_advantages(
                jnp.asarray(rewards), tcfg.grpo.adv_eps)).reshape(-1)

            # NAT selection
            rmask = jnp.asarray(rb.response_mask)
            if isinstance(self.selector, EntropySelector):
                sel = self.selector(tg.key_sel, rmask,
                                    jnp.asarray(rb.entropies))
            else:
                sel = self.selector(tg.key_sel, rmask)
            ht_w = np.asarray(sel.ht_weights, np.float32)
            keep_len = np.asarray(sel.keep_len)

            batch = {
                "tokens": rb.tokens,
                "response_mask": rb.response_mask,
                "old_logp": rb.old_logp,
                "advantages": adv.astype(np.float32),
                "ht_weights": ht_w,
                "orig_lengths": rb.response_lens.astype(np.float32),
                "lengths": (rb.prompt_lens
                            + rb.response_lens).astype(np.int32),
                # staleness-corrected HT objective (DESIGN.md §6): the
                # engine's in-flight logprobs are the behaviour policy; rows
                # that lag the learner version get the truncated-IS
                # correction in the loss
                "behavior_logp": rb.old_logp,
                "staleness": np.full((rb.tokens.shape[0],), staleness,
                                     np.float32),
            }

        # batch layout (core/layout.py): bucketed slicing, hull packing, or
        # the raw padded grid — the selection above is layout-invariant
        with span("nat.layout"):
            lb = self.layout.build(
                batch, prompt_lens=rb.prompt_lens,
                response_lens=rb.response_lens, keep_len=keep_len,
                keep_mask=ht_w > 0, prefix_structured=sel.prefix_structured,
                ladder=self.ladder)
        batch = lb.data
        t_sel = time.perf_counter()

        with span("nat.learn"):
            with span("nat.learn.dispatch"):
                self.params, self.opt_state, metrics = self._train_step(
                    self.params, self.opt_state,
                    {k: jnp.asarray(v) for k, v in batch.items()})
            with span("nat.learn.sync"):
                metrics = {k: float(v) for k, v in metrics.items()}
            with span("nat.publish"):
                self._publish()
            t_end = time.perf_counter()

        rstats = rb.stats or {}
        metrics.update(
            reward_mean=float(rewards.mean()),
            reward_max=float(rewards.max(axis=1).mean()),
            completed_frac=float(rb.completed.mean()),
            resp_len_mean=float(rb.response_lens.mean()),
            bucket_len=lb.row_len,
            # layout accounting (DESIGN.md §7): tokens the update physically
            # scored and the kept-budget fraction of them — the learner-side
            # twin of rollout_utilization below
            tokens_scored=lb.tokens_scored,
            learner_rows=lb.num_rows,
            pack_efficiency=lb.pack_efficiency,
            # rollout token cost: with the slot arena, over-provisioned groups
            # pay for generated tokens, not G' full budgets (ISSUE 2)
            tokens_generated=int(rstats.get("tokens_generated", 0)),
            tokens_budget=int(rstats.get("tokens_budget", 0)),
            rollout_decode_steps=int(rstats.get("decode_steps", 0)),
            # engine rounds and their host split (rl/engine.py): seconds
            # blocked reading the control planes, and host seconds after
            rollout_rounds=int(rstats.get("rounds", 0)),
            rollout_sync_s=float(rstats.get("sync_s", 0.0)),
            rollout_host_s=float(rstats.get("host_s", 0.0)),
            rollout_cancelled=int(rstats.get("cancelled", 0)),
            rollout_utilization=(
                rstats.get("tokens_generated", 0)
                / max(rstats.get("slot_substeps", 0), 1)),
            entropy_behavior=float(
                (rb.entropies * rb.response_mask).sum()
                / max(rb.response_mask.sum(), 1)),
            # overlap bookkeeping
            policy_version=self._learner_version,
            behavior_version=tg.behavior_version,
            staleness=staleness,
            dropped_stale=self.queue.dropped_stale,
            time_rollout=tg.t_rollout,
            time_wait=t_roll - t0,
            time_select=t_sel - t_roll,
            time_learn=t_end - t_sel,
            time_total=t_end - t0,
            step=self.step_count,
        )
        self.step_count += 1
        self.history.append(metrics)
        return metrics

    def run(self, num_steps: int, log_every: int = 0) -> list:
        for i in range(num_steps):
            m = self.train_step()
            if log_every and i % log_every == 0:
                print(f"step {m['step']:4d} reward={m['reward_mean']:.3f} "
                      f"loss={m['loss']:+.4f} sel={m.get('selected_ratio', 1):.2f} "
                      f"rows={m['learner_rows']}x{m['bucket_len']} "
                      f"eff={m['pack_efficiency']:.2f} t={m['time_total']:.2f}s")
        return self.history

    # --------------------------------------------------------------- lifecycle
    def close(self) -> None:
        """Stop the actor thread (idempotent, *terminal*): queued groups are
        dropped and the sample queue is poisoned, so a producer blocked on a
        full queue exits instead of leaking, and any later ``train_step``
        raises instead of hanging."""
        self._stop_evt.set()
        with self._cv:
            self._cv.notify_all()
        self.queue.fail(RuntimeError("trainer closed"))
        if self._actor is not None:
            self._actor.join(timeout=10.0)
            self._actor = None

    def _quiesce(self, timeout: float = 300.0) -> None:
        """Pause admission and wait for in-flight rollouts to deposit.
        Queued groups stay queued — the checkpoint cursor rewinds past them
        (``TaggedGroup.key0``), so quiescing never runs hidden learner
        steps and checkpoint cadence cannot change the training stream."""
        with self._cv:
            self._paused = True
            self._cv.notify_all()
        if self._actor is None or not self._actor.is_alive():
            return
        deadline = time.monotonic() + timeout
        while True:
            if self._actor_idle.is_set() and not self._stream_groups:
                return
            if not self._actor.is_alive():
                return
            if time.monotonic() > deadline:
                raise TimeoutError("actor failed to quiesce")
            time.sleep(0.005)

    def _resume_admission(self) -> None:
        with self._cv:
            self._paused = False
            self._cv.notify_all()

    # -------------------------------------------------------------- checkpoint
    def save_checkpoint(self, mgr, blocking: bool = True) -> int:
        """Pause admission, wait for in-flight rollouts to deposit, persist
        params/opt plus the async cursors.  Unconsumed rollout data is
        never serialized and never flushed: the saved actor cursor rewinds
        to the oldest unconsumed group (its pre-roll key-chain state rides
        in the queue), so resume re-rolls it — under the same params for
        the serial path, which makes staleness-0 resume token-exact.  For
        ``max_staleness > 0`` the snapshot is a clean group boundary; the
        restored run re-rolls from a fresh engine session, so its sample
        stream is valid (exact behaviour logprobs, staleness bound intact)
        but not bit-identical to the uninterrupted run."""
        try:
            self._quiesce()
            head = self.queue.peek()
            if head is not None:
                saved_next, saved_key = head.index, head.key0
            else:
                saved_next, saved_key = self._next_group, self._actor_key
            tree = {"params": self.params, "opt": self.opt_state}
            extra = {
                "learner_version": int(self._learner_version),
                "step_count": int(self.step_count),
                "next_group": int(saved_next),
                "actor_key": np.asarray(saved_key).tolist(),
                "eval_key": np.asarray(self.key).tolist(),
                "pipeline": {"step": int(saved_next),
                             "seed": self.pipeline.seed},
                "max_staleness": int(self.tcfg.max_staleness),
            }
            mgr.save(self._learner_version, tree, extra, blocking=blocking)
        finally:
            self._resume_admission()
        return int(self._learner_version)

    def restore_checkpoint(self, mgr, step: Optional[int] = None) -> dict:
        """Restore params/opt and the async cursors saved by
        ``save_checkpoint``.  Must be called before the actor starts (i.e.
        before the first ``train_step`` of this instance)."""
        if self._actor is not None and self._actor.is_alive():
            raise RuntimeError("restore_checkpoint before the first train_step")
        if step is None:
            step = mgr.latest_step()
        tree, extra = mgr.restore(
            step, {"params": self.params, "opt": self.opt_state})
        self.params, self.opt_state = tree["params"], tree["opt"]
        self._learner_version = int(extra["learner_version"])
        self.step_count = int(extra["step_count"])
        self._next_group = int(extra["next_group"])
        self._actor_key = jnp.asarray(np.array(extra["actor_key"], np.uint32))
        self.key = jnp.asarray(np.array(extra["eval_key"], np.uint32))
        self.pipeline.load_state_dict(extra["pipeline"])
        self._published = (self.params, self._learner_version)
        return extra

    # ------------------------------------------------------------------ eval
    def evaluate(self, num_prompts: int = 32, temperature: float = 0.0) -> dict:
        """Greedy accuracy on fresh prompts (reward == 1 counts as correct).

        Uses the legacy single-wave path: eval is G=1 with no
        over-provisioning, so there is no recycling for the arena to
        exploit, and the training engine's jit cache (keyed on the training
        RolloutConfig) is left untouched."""
        from repro.data.pipeline import PromptPipeline

        pipe = PromptPipeline(self.env, batch_size=num_prompts,
                              max_prompt_len=self.tcfg.max_prompt_len,
                              seed=self.tcfg.seed + 10_000)
        pb = next(pipe)
        rcfg = dataclasses.replace(self.tcfg.rollout, temperature=temperature,
                                   group_size=1, overprovision=1.0)
        self.key, k = jax.random.split(self.key)
        rb = rollout_group(self.params, self.model_cfg, rcfg,
                           pb.tokens, pb.prompt_lens, k)
        correct = 0
        for i in range(num_prompts):
            pl, rl = int(rb.prompt_lens[i]), int(rb.response_lens[i])
            r = self.env.reward(pb.prompts[i], rb.tokens[i, pl:pl + rl])
            correct += int(r >= 1.0)
        return {"accuracy": correct / num_prompts,
                "resp_len": float(rb.response_lens.mean())}
