"""Rollout engine: host milliseconds per engine round over the window, the
sum of the window steps' ``rollout_host_s`` over the sum of their
``rollout_rounds``.  ``rollout_host_s`` is the engine's ``stats["host_s"]``
(rl/engine.py): the seconds from the return of each round's blocking read
of the control planes (span ``nat.engine.sync``) to the return of its
dispatch (span ``nat.engine.dispatch``), spent in harvest and placement
while the device, its step done, waits.  Nothing where the program has no
such counter."""


def read(run):
    steps = [m for m in run.window if "rollout_host_s" in m]
    rounds = sum(m["rollout_rounds"] for m in steps)
    if rounds <= 0:
        return None
    return 1e3 * sum(m["rollout_host_s"] for m in steps) / rounds
