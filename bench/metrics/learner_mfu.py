"""Learner and optimizer: the learner's required operations (bench/flops.py,
kept hulls only) over the summed ``time_learn`` of the window, as a share
of the chip's bf16 peak (bench/peaks.py)."""


def read(run):
    t = sum(m["time_learn"] for m in run.window)
    if t <= 0 or not run.learner_flops:
        return None
    return 100.0 * sum(run.learner_flops) / t / run.peak_flops
