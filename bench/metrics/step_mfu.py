"""Whole step: required rollout and learner operations of the window's
steps (bench/flops.py) over the window, as a share of the chip's bf16
peak."""


def read(run):
    if run.window_s <= 0 or not run.learner_flops:
        return None
    work = sum(run.rollout_flops) + sum(run.learner_flops)
    return 100.0 * work / run.window_s / run.peak_flops
