"""Rollout engine: mean host-clock seconds of a window step's rollout (the
trainer's ``time_rollout``, which ends in a host read of the batch)."""


def read(run):
    if not run.window:
        return None
    return sum(m["time_rollout"] for m in run.window) / len(run.window)
