"""Output tokens the window's steps produced over the window's seconds."""


def read(run):
    w = run.window
    return sum(s.tokens for s in w.steps) / w.seconds if w.steps else None
