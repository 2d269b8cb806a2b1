"""Selector and layout: mean host-clock seconds of a window step between the
rollout and the learner (rewards, token selection, layout build; the
trainer's ``time_select``)."""


def read(run):
    if not run.window:
        return None
    return sum(m["time_select"] for m in run.window) / len(run.window)
