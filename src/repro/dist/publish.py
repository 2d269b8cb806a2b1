"""Device-to-device weight publication (DESIGN.md §12).

The learner's params live sharded on the learner slice; every rollout
fleet wants a replicated snapshot on *its* slice.  The naive path — gather
to host, then feed each engine — serializes the whole parameter tree
through host RAM once per optimizer step and stalls both sides.  This
module reshards instead: one ``jax.device_put`` per fleet target moves the
tree straight between device buffers (ICI/NVLink on real backends, a
buffer copy on CPU), never materializing a host copy.

Epoch protocol: each ``publish`` call stamps a monotonically increasing
``epoch``; ``latest(name)`` returns the newest snapshot for that target.
The trainer maps epochs 1:1 onto learner versions, so the SampleQueue's
staleness contract (version-tagged groups, PR 3) is unchanged — a fleet
actor that picks up ``latest`` at admission produces a group whose
``behavior_version`` is exactly the snapshot's epoch.

"Zero bytes through the host" is asserted two ways:

* **counter-exact** — ``host_bytes`` counts bytes moved via any host
  staging path.  The device_put path never stages, so the counter stays 0
  by construction; the parity test and ``check_gates.py`` ceiling assert
  it stays that way (ABSOLUTE_ONLY: exempt from wall-time noise).
* **belt-and-braces** — publication runs under
  ``jax.transfer_guard_device_to_host("disallow")``.  On CPU the guard is
  inert (host platform "transfers" are aliasing, so nothing fires —
  which is why the counter, not the guard, is the gate), but on real
  backends it turns an accidental host gather into a hard error.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax


class PublicationError(RuntimeError):
    """Publication failed after exhausting its bounded retry budget."""


def tree_bytes(tree: Any) -> int:
    """Total payload size of a pytree of arrays, in bytes."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is not None and dtype is not None:
            total += int(size) * dtype.itemsize
    return total


class WeightPublisher:
    """Reshards learner params onto each rollout slice's replicated layout.

    ``targets`` maps a fleet name to a placement: either a single device
    (the common fully-replicated engine layout) or a ``Sharding``.  The
    publisher is thread-safe — the learner publishes from the train loop
    while fleet actor threads read ``latest`` at group admission.

    ``max_attempts``/``backoff_s`` bound the retry loop around the
    device_put sweep (DESIGN.md §13): a transient failure (an injected
    fault, a flaky interconnect on real backends) is retried with doubling
    backoff and counted in ``publish_retries``; exhausting the budget
    escalates as ``PublicationError`` — never a silent spin.
    """

    def __init__(self, targets: Dict[str, Any], *, max_attempts: int = 1,
                 backoff_s: float = 0.05):
        if not targets:
            raise ValueError("WeightPublisher needs at least one target")
        self._targets = dict(targets)
        self._lock = threading.Lock()
        self._latest: Dict[str, Tuple[Any, int]] = {}
        self._max_attempts = max(1, int(max_attempts))
        self._backoff_s = float(backoff_s)
        # fault-injection hook (testing/chaos.py, DESIGN.md §13): fired
        # inside the retry loop so injected failures exercise it
        self.chaos = None
        self.stats: Dict[str, int] = {
            "publishes": 0,
            "bytes_published": 0,
            "host_bytes": 0,
            "publish_retries": 0,
            "epoch": 0,
        }

    @property
    def targets(self) -> Dict[str, Any]:
        return dict(self._targets)

    def publish(self, params: Any, *, epoch: Optional[int] = None) -> Dict[str, Any]:
        """Snapshot ``params`` onto every target, device-to-device.

        Returns ``{name: resharded_tree}``.  ``epoch`` defaults to the
        next integer after the last published epoch.
        """
        with jax.profiler.TraceAnnotation("nat.publish"), self._lock:
            if epoch is None:
                epoch = self.stats["epoch"] + 1
            nbytes = tree_bytes(params)
            for attempt in range(1, self._max_attempts + 1):
                try:
                    if self.chaos is not None:
                        self.chaos.fire("publish", index=int(epoch))
                    out: Dict[str, Any] = {}
                    with jax.transfer_guard_device_to_host("disallow"):
                        for name, placement in self._targets.items():
                            out[name] = jax.device_put(params, placement)
                    break
                except Exception as e:
                    if attempt >= self._max_attempts:
                        raise PublicationError(
                            f"publication of epoch {epoch} failed after "
                            f"{self._max_attempts} attempts") from e
                    self.stats["publish_retries"] += 1
                    time.sleep(self._backoff_s * 2 ** (attempt - 1))
            for name, tree in out.items():
                self._latest[name] = (tree, epoch)
            self.stats["publishes"] += 1
            self.stats["bytes_published"] += nbytes * len(self._targets)
            self.stats["epoch"] = int(epoch)
            return out

    def add_target(self, name: str, placement: Any, params: Any = None,
                   *, epoch: Optional[int] = None) -> Any:
        """Register a publication target mid-run (fleet elasticity,
        DESIGN.md §13).  With ``params``, the current snapshot is pushed
        to the newcomer immediately, stamped with ``epoch`` (default: the
        publisher's current epoch) — the joiner starts at the fleet's
        publication epoch instead of waiting a step.  Returns the
        resharded tree (or None without ``params``)."""
        with self._lock:
            if name in self._targets:
                raise ValueError(f"target {name!r} already registered")
            self._targets[name] = placement
            if params is None:
                return None
            e = self.stats["epoch"] if epoch is None else int(epoch)
            with jax.transfer_guard_device_to_host("disallow"):
                tree = jax.device_put(params, placement)
            self._latest[name] = (tree, e)
            self.stats["bytes_published"] += tree_bytes(params)
            return tree

    def remove_target(self, name: str) -> None:
        """Stop publishing to a departed replica (its last snapshot is
        dropped too — a rejoin under the same name starts fresh)."""
        with self._lock:
            self._targets.pop(name, None)
            self._latest.pop(name, None)

    def latest(self, name: str) -> Tuple[Any, int]:
        """Newest ``(params, epoch)`` snapshot for target ``name``."""
        with self._lock:
            if name not in self._latest:
                raise KeyError(
                    f"no snapshot published yet for target {name!r}")
            return self._latest[name]
