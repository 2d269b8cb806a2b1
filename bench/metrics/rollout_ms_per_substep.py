"""Rollout engine: milliseconds per decode substep over the window, the
sum of ``time_rollout`` over the sum of the engine's decode substeps."""


def read(run):
    substeps = sum(m["rollout_decode_steps"] for m in run.window)
    if substeps <= 0:
        return None
    return 1e3 * sum(m["time_rollout"] for m in run.window) / substeps
