"""Disaggregated actor/learner: replicated rollout fleets over mesh
slices, device-to-device weight publication (DESIGN.md §12), and
chaos-hardened supervision with token-exact failure recovery
(DESIGN.md §13).

``AsyncNATGRPOTrainer`` (PR 3) overlaps one rollout engine with one
learner in a single process; the weight "publication" is an in-process
reference swap and every flop shares one device set.  This trainer scales
the same bounded-staleness design out across a carved topology
(``dist/placement.py``):

* the **learner** keeps the sharded train step on its own slice,
* **N fleet replicas** each own a slice-pinned rollout engine and an actor
  thread, all pulling prompts from the shared deterministic pipeline by
  index and depositing into one multi-producer ``SampleQueue`` that
  reassembles the serial index order (reservations mark in-flight gaps),
* **publication** reshards the learner params straight onto every fleet
  slice with ``jax.device_put`` (``dist/publish.py``) — zero bytes through
  the host, one epoch per learner version, so the staleness contract is
  unchanged: a group rolled from epoch ``e`` params has
  ``behavior_version == e``,
* with ``disagg="prefill,decode"`` each fleet slice further splits into a
  prefill cell and a paged decode arena
  (``rl/engine.py::DisaggPagedRolloutEngine``), handing groups off by
  block table through the page pool,
* a **ReplicaSupervisor** (``rl/supervision.py``) heartbeats every actor,
  reclaims a dead/hung replica's claimed group index for a survivor to
  re-roll token-exactly off the shared ``KeyChain``, and admits replicas
  *joining* mid-run (``add_replica``: a fresh slice-pinned engine
  receiving the current publication epoch, claiming from the next clean
  group boundary).

Determinism contract: group ``i``'s rollout keys come from the shared
``KeyChain`` — the exact splits the serial walk produces — and the queue
serves groups in index order, so a fleet of 1 at staleness 0 is
**bit-exact** against ``NATGRPOTrainer``, and any fleet's group ``i`` is
token-exact against a single-engine oracle rolling the same index under
the same params (``tests/test_dist_trainer.py``).  What a fleet of N
changes is only *which version's params* a group sees within the
staleness bound — the same freedom PR 3's single actor already had.
The same property is what makes failure recovery deterministic: a
reclaimed index re-derives the dead claimer's exact keys, so a fleet of
2 with one killed replica produces the same per-group tokens as the
no-fault fleet (``tests/test_supervision.py``).
"""
from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import Optional

import jax

from repro.dist import SliceTopology, WeightPublisher, carve
from repro.models import capabilities as caps
from repro.models.config import ModelConfig
from repro.rl.async_trainer import (
    AsyncNATGRPOTrainer, KeyChain, NATTrainerConfig, TaggedGroup,
)
from repro.rl.learner import with_publication
from repro.rl.rollout import rollout_group_continuous
from repro.rl.supervision import (
    QuiesceTimeout, ReplicaSupervisor, RetryPolicy, SupervisorError,
    retry_call,
)


def _parse_disagg(spec: str) -> bool:
    if not spec:
        return False
    roles = {r.strip() for r in spec.split(",") if r.strip()}
    if roles != {"prefill", "decode"}:
        raise ValueError(
            f"disagg must be '' or 'prefill,decode', got {spec!r}")
    return True


@dataclass
class FleetReplica:
    """One fleet member's runtime record — replicas are dynamic now
    (supervised death, elastic join), so the roster lives here rather
    than being read off the static topology."""

    name: str
    engine: object
    device: object
    prefill_device: object = None
    idle: threading.Event = field(default_factory=threading.Event)
    thread: Optional[threading.Thread] = None


class DistNATGRPOTrainer(AsyncNATGRPOTrainer):
    """Fleet-replicated, slice-placed NAT-GRPO trainer.

    ``devices`` (default ``jax.devices()``) is carved into a learner slice
    plus ``tcfg.fleet`` rollout slices; on a single-device host every
    slice degenerates to that device and only the placement collapses —
    the orchestration (fleet threads, ordered reassembly, publication
    epochs) runs identically, which is what the parity tests pin.
    """

    def __init__(self, model_cfg: ModelConfig, tcfg: NATTrainerConfig,
                 params=None, mesh=None, rules=None, budget_fn=None,
                 devices=None, chaos=None):
        fleet = max(1, int(tcfg.fleet))
        disagg = _parse_disagg(tcfg.disagg)
        if disagg:
            if tcfg.rollout_engine != "paged":
                raise ValueError(
                    "disagg='prefill,decode' requires rollout_engine="
                    f"'paged' (got {tcfg.rollout_engine!r}): the handoff "
                    "contract is the paged pool's block tables")
            caps.check_slice_handoff(model_cfg)
        super().__init__(model_cfg, tcfg, params=params, mesh=mesh,
                         rules=rules, budget_fn=budget_fn, chaos=chaos)
        if self.engine is None:
            raise ValueError(
                "the disaggregated trainer needs a rollout engine "
                f"(rollout_engine={tcfg.rollout_engine!r} resolved to the "
                "legacy scan — no arena to pin to a slice)")

        self._disagg = disagg
        self.topology: SliceTopology = carve(devices, fleet=fleet,
                                             disagg=disagg)
        # one slice-pinned replica per fleet; replica 0 doubles as
        # self.engine so the parent's inline staleness-0 path (and its
        # introspection) runs on a fleet slice, not a detached engine
        self._replicas: list[FleetReplica] = []
        for fs in self.topology.fleets:
            eng = self._build_engine(
                device=fs.decode[0],
                prefill_device=fs.prefill[0] if disagg else None)
            eng.chaos = chaos
            eng.chaos_replica = fs.name
            self._replicas.append(FleetReplica(
                name=fs.name, engine=eng, device=fs.decode[0],
                prefill_device=fs.prefill[0] if disagg else None))
        self._replica_serial = len(self._replicas)  # next join's number
        self.fleet_engines = [r.engine for r in self._replicas]
        self.engine = self.fleet_engines[0]

        # device-to-device publication: one replicated target per fleet
        # slice, epochs mapped 1:1 onto learner versions (epoch 0 = init).
        # The train step itself carries the publication hook, so the
        # snapshot dispatch overlaps the metrics fetch that follows it;
        # _publish() then just swaps the version-tagged references.
        # Transient publication failures retry with bounded backoff
        # (DESIGN.md §13) before escalating as PublicationError.
        self.publisher = WeightPublisher(
            {r.name: r.device for r in self._replicas},
            max_attempts=max(1, tcfg.publish_retries),
            backoff_s=tcfg.publish_backoff)
        self.publisher.chaos = chaos
        self._train_step = with_publication(self._train_step, self.publisher)
        pub = self.publisher.publish(self.params, epoch=0)
        self._published_f = {name: (tree, 0) for name, tree in pub.items()}
        self._published = (pub[self._replicas[0].name], 0)

        # shared serial key chain: whichever replica claims group i gets
        # the exact keys the serial walk would have produced for it
        self._key_chain = KeyChain(self._actor_key, self._next_group)
        self._fleet_threads: list = []
        self._placement_retries = 0

        # supervision (DESIGN.md §13): heartbeat monitor + reclaim heap.
        # The supervisor lock is a leaf under self._cv, and its wake
        # callback runs outside that lock — see supervision.py.
        self.supervisor: Optional[ReplicaSupervisor] = None
        if tcfg.supervise:
            self.supervisor = ReplicaSupervisor(
                self.queue, hang_timeout=tcfg.hang_timeout,
                interval=tcfg.supervise_interval, wake=self._wake_actors)

    def _wake_actors(self) -> None:
        with self._cv:
            self._cv.notify_all()

    # ------------------------------------------------------------- actor side
    def _ensure_actor(self) -> None:
        if self.tcfg.max_staleness == 0:
            return  # inline production on fleet slice 0, no threads
        if self._fleet_threads:
            # already launched: replica lifecycle now belongs to the
            # supervisor (dead replicas are not silently resurrected —
            # use add_replica to restore capacity)
            return
        self._stop_evt.clear()
        for rep in self._replicas:
            self._spawn_replica_thread(rep)
        self._actor = self._fleet_threads[0]  # parent lifecycle hooks
        if self.supervisor is not None:
            self.supervisor.start()

    def _spawn_replica_thread(self, rep: FleetReplica,
                              joined: bool = False) -> None:
        t = threading.Thread(target=self._fleet_main, args=(rep,),
                             daemon=True, name=f"nat-actor-{rep.name}")
        rep.thread = t
        self._fleet_threads.append(t)
        if self.supervisor is not None:
            eng = rep.engine
            self.supervisor.register(
                rep.name, thread=t, joined=joined,
                # progress watermark: completed drive rounds + decode
                # steps — a long-but-advancing rollout is not a hang
                progress=lambda e=eng: (int(e.stats.get("rounds", 0)),
                                        int(e.stats.get("decode_steps", 0))))
        t.start()

    def _fleet_main(self, rep: FleetReplica) -> None:
        """Replica thread entry: route failures to the supervisor (which
        reclaims the claimed group and keeps the run alive) — or, when
        supervision is off, poison the queue like the PR 3 single actor."""
        try:
            self._actor_fleet(rep)
        except BaseException as e:
            if self._stop_evt.is_set():
                # close() poisoned the queue under a blocked put/reserve:
                # an orderly shutdown, not a replica death to reclaim (and
                # retiring would wipe the fleet's watermarks)
                return
            if self.supervisor is not None:
                self.supervisor.report_failure(rep.name, e)
            else:
                self.queue.fail(e)

    def _actor_fleet(self, rep: FleetReplica) -> None:
        """One fleet replica's loop: claim the next group index under the
        staleness gate (taking any reclaimed orphan index first), roll it
        on this replica's slice under the newest published snapshot,
        deposit in index order (per-group sessions — the chain keys make
        every group independently reproducible)."""
        sup = self.supervisor
        name, engine, idle = rep.name, rep.engine, rep.idle
        while not self._stop_evt.is_set():
            with self._cv:
                # wait while there is nothing to do: no orphaned index to
                # reclaim (reclaims proceed even when paused — they are
                # already-admitted work a quiesce must drain) and either
                # admission is paused or the staleness gate is shut
                while (not self._stop_evt.is_set()
                       and not (sup is not None
                                and (sup.should_stop(name)
                                     or sup.reclaim_pending()))
                       and (self._paused
                            or not self._gate_open(self._next_group))):
                    idle.set()
                    if sup is not None:
                        sup.heartbeat(name)
                    self._cv.wait(0.05)
                if self._stop_evt.is_set():
                    return
                if sup is not None and sup.should_stop(name):
                    idle.set()
                    return
                idle.clear()
                i = sup.take_reclaim(name) if sup is not None else None
                if i is None and (self._paused or not
                                  self._gate_open(self._next_group)):
                    continue  # lost a reclaim race; re-enter the wait
                if i is None:
                    i = self._next_group
                    pb = self.pipeline.batch_at(i)
                    self.pipeline.step = max(self.pipeline.step, i + 1)
                    self._next_group = i + 1
                    # keep the parent's checkpoint cursor honest:
                    # _actor_key is always the chain state before the next
                    # unclaimed group
                    self._actor_key = self._key_chain.state_before(i + 1)
                    # claim the queue slot inside the lock: pop must know
                    # this index is in flight before any younger deposit
                    # can land.  The gate bounds outstanding groups to
                    # <= capacity, so this never blocks; the timeout
                    # surfaces contract bugs.
                    self.queue.reserve(i, timeout=600.0)
                    if sup is not None:
                        sup.claim(name, i)
                else:
                    # reclaimed orphan: its reservation survived its dead
                    # claimer (pop is still holding younger groups for
                    # it), and the pipeline/key cursors already passed it
                    pb = self.pipeline.batch_at(i)
                key0, k_roll, k_sel = self._key_chain.keys_for(i)
                params, version = self._published_f[name]
            if sup is not None:
                sup.heartbeat(name)
            if self.chaos is not None:
                # injected death/stall lands after the claim, while the
                # reservation is live — the exact window reclaim covers
                self.chaos.fire("actor", replica=name, index=i)
            t0 = time.perf_counter()
            try:
                rb = self._roll_group(engine, params, pb, k_roll, i)
            except BaseException:
                if sup is None:
                    self.queue.cancel(i)  # unblock pop before fail() lands
                # supervised: keep the reservation — report_failure will
                # push i onto the reclaim heap and a survivor adopts it
                raise
            self.queue.put(
                TaggedGroup(index=i, behavior_version=version, batch=rb,
                            prompt_batch=pb, key_sel=k_sel,
                            t_rollout=time.perf_counter() - t0, key0=key0),
                producer=name)
            if sup is not None:
                sup.delivered(name, i)
                sup.heartbeat(name)

    def _roll_group(self, engine, params, pb, k_roll, i: int):
        """Roll group ``i`` on ``engine`` — split out so chaos/property
        tests can substitute a deterministic fake roll.  Transient
        ``PagePoolExhausted`` (pool pressure from a draining previous
        session, or injected) is retried with bounded backoff on a fresh
        per-group session; persistent exhaustion escalates after
        ``tcfg.placement_retries`` attempts — never a silent spin."""
        from repro.rl.engine import PagePoolExhausted

        def roll():
            return rollout_group_continuous(
                params, self.model_cfg, self.tcfg.rollout,
                pb.tokens, pb.prompt_lens, k_roll, engine=engine,
                budgets=self._budgets_for(i))

        def on_retry(attempt, exc):
            self._placement_retries += 1

        return retry_call(
            roll,
            RetryPolicy(max_attempts=max(1, self.tcfg.placement_retries),
                        backoff_s=self.tcfg.placement_backoff),
            (PagePoolExhausted,), on_retry)

    # ----------------------------------------------------------- elasticity
    def add_replica(self, *, name: Optional[str] = None, device=None,
                    prefill_device=None) -> str:
        """Join a fresh replica mid-run (fleet elasticity, DESIGN.md §13).

        The handshake: build a slice-pinned engine (device defaults to
        round-robin over the carved fleet slices — i.e. a replacement
        lands on the dead replica's slice), register it as a publication
        target and push it the *current* epoch's params, add it to the
        published map and the roster, then start its actor thread.  All
        under the trainer lock, so the newcomer's first claim is the next
        clean group boundary — it can never see a group the fleet already
        claimed, and its first deposit carries the current epoch's
        ``behavior_version``.  Call between train steps (learner thread).
        """
        with self._cv:
            n = self._replica_serial
            self._replica_serial += 1
            fs = self.topology.fleets[n % self.topology.num_fleets]
            if name is None:
                name = f"fleet{n}"
            if any(r.name == name for r in self._replicas):
                raise ValueError(f"replica {name!r} already exists")
            dev = device if device is not None else fs.decode[0]
            pdev = (prefill_device if prefill_device is not None
                    else (fs.prefill[0] if self._disagg else None))
            eng = self._build_engine(device=dev, prefill_device=pdev)
            eng.chaos = self.chaos
            eng.chaos_replica = name
            tree = self.publisher.add_target(
                name, dev, params=self.params, epoch=self._learner_version)
            self._published_f[name] = (tree, self._learner_version)
            rep = FleetReplica(name=name, engine=eng, device=dev,
                               prefill_device=pdev)
            self._replicas.append(rep)
            self.fleet_engines.append(eng)
            started = bool(self._fleet_threads)
        if started:
            self._spawn_replica_thread(rep, joined=True)
        return name

    # ----------------------------------------------------------- learner side
    def _publish(self) -> None:
        with self._cv:
            self._learner_version += 1
            pub = {}
            for rep in self._replicas:
                tree, epoch = self.publisher.latest(rep.name)
                if epoch != self._learner_version:
                    raise RuntimeError(
                        f"publication epoch {epoch} != learner version "
                        f"{self._learner_version}: the train step's "
                        "with_publication hook is out of sync")
                pub[rep.name] = tree
            self._published_f = {name: (tree, self._learner_version)
                                 for name, tree in pub.items()}
            self._published = (pub[self._replicas[0].name],
                               self._learner_version)
            self._cv.notify_all()

    # --------------------------------------------------------------- lifecycle
    def close(self) -> None:
        if self.supervisor is not None:
            self.supervisor.stop()
        super().close()  # joins thread 0 via self._actor
        stuck = []
        for rep in self._replicas:
            if rep.thread is None:
                continue
            rep.thread.join(timeout=10.0)
            if rep.thread.is_alive():
                stuck.append(rep.name)
        self._fleet_threads = []
        if stuck:
            # close() must not raise, but the operator needs to know who
            # wedged and in what state — the structured report names each
            # replica's claimed group, watermark, and heartbeat age
            warnings.warn(
                "close(): fleet threads failed to join within 10.0s — "
                + self._replica_report(stuck), RuntimeWarning,
                stacklevel=2)

    def _replica_report(self, names=None) -> str:
        """One structured line per replica: identity, liveness, claimed
        group, queue watermark, heartbeat age — the error payload for
        quiesce/join timeouts (DESIGN.md §13)."""
        sup_status = {}
        if self.supervisor is not None:
            sup_status = {s.name: s for s in self.supervisor.status()}
        lines = []
        for rep in self._replicas:
            if names is not None and rep.name not in names:
                continue
            s = sup_status.get(rep.name)
            alive = rep.thread.is_alive() if rep.thread is not None else False
            hb = f"{s.heartbeat_age:.1f}s" if s is not None else "n/a"
            claimed = s.claimed if s is not None else None
            state = ("dead" if s is not None and s.dead else
                     "condemned" if s is not None and s.condemned else
                     "alive" if alive else "not-started")
            lines.append(
                f"{rep.name}: state={state} idle={rep.idle.is_set()} "
                f"claimed={claimed} "
                f"watermark={self.queue.watermarks.get(rep.name)} "
                f"heartbeat_age={hb}")
        return "; ".join(lines)

    def _quiesce(self, timeout: float = 300.0) -> None:
        with self._cv:
            self._paused = True
            self._cv.notify_all()
        deadline = time.monotonic() + timeout
        while True:
            # checked before the settled test: a fleet whose every thread
            # already exited would otherwise "settle" trivially and let a
            # checkpoint save proceed over a failed run
            if self.supervisor is not None and self.supervisor.all_dead():
                raise SupervisorError(
                    "cannot quiesce: every fleet replica is dead or "
                    "condemned — " + self._replica_report(),
                    self.supervisor.status())
            settled = all(rep.idle.is_set()
                          or rep.thread is None
                          or not rep.thread.is_alive()
                          for rep in self._replicas)
            if settled and self.queue.inflight() == 0:
                return
            if time.monotonic() > deadline:
                raise QuiesceTimeout(
                    f"fleet actors failed to quiesce within {timeout:.0f}s"
                    f" — " + self._replica_report())
            time.sleep(0.005)

    # -------------------------------------------------------------- checkpoint
    def restore_checkpoint(self, mgr, step: Optional[int] = None) -> dict:
        extra = super().restore_checkpoint(mgr, step)
        # re-seed the chain at the restored cursor and re-publish the
        # restored params as the current epoch on every fleet slice
        self._key_chain = KeyChain(self._actor_key, self._next_group)
        pub = self.publisher.publish(self.params,
                                     epoch=self._learner_version)
        self._published_f = {name: (tree, self._learner_version)
                             for name, tree in pub.items()}
        self._published = (pub[self._replicas[0].name],
                           self._learner_version)
        return extra

    # ------------------------------------------------------------------ stats
    def publication_stats(self) -> dict:
        """Publisher counters + per-replica version watermarks — the
        zero-host-bytes gate reads ``host_bytes`` from here, the recovery
        gates read ``publish_retries``/``groups_reclaimed``."""
        stats = dict(self.publisher.stats)
        stats["watermarks"] = dict(self.queue.watermarks)
        stats["dropped_dup"] = int(self.queue.dropped_dup)
        stats["placement_retries"] = int(self._placement_retries)
        if self.supervisor is not None:
            stats["supervisor"] = dict(self.supervisor.stats)
        if hasattr(self.engine, "stats"):
            stats["handoffs"] = int(self.engine.stats.get("handoffs", 0))
            stats["handoff_bytes"] = int(
                self.engine.stats.get("handoff_bytes", 0))
        return stats


def make_dist_trainer(model_cfg: ModelConfig, tcfg: NATTrainerConfig,
                      **kw) -> AsyncNATGRPOTrainer:
    """Config-dispatched constructor: fleet/disagg set -> the dist trainer,
    otherwise the plain async trainer (what ``launch/train.py`` calls)."""
    if tcfg.fleet or tcfg.disagg:
        return DistNATGRPOTrainer(model_cfg, tcfg, **kw)
    return AsyncNATGRPOTrainer(model_cfg, tcfg, **kw)
