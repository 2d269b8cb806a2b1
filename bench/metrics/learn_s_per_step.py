"""Learner and optimizer: mean host-clock seconds of a window step's
learner update (the trainer's ``time_learn``: batch upload, forward,
backward, AdamW, and the read of the step's metrics)."""


def read(run):
    if not run.window:
        return None
    return sum(m["time_learn"] for m in run.window) / len(run.window)
