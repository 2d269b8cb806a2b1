"""Selector and layout: mean over the window's steps of kept tokens over the
tokens the learner scores (the layout's ``pack_efficiency``)."""


def read(run):
    if not run.window:
        return None
    return sum(m["pack_efficiency"] for m in run.window) / len(run.window)
