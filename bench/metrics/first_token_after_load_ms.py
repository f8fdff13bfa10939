"""Mean time from LOAD's return to the probe's first token, in ms."""


def read(run):
    s = run.window.scaleouts
    if not s:
        return None
    return sum(x.first_token_t - x.t_loaded for x in s) / len(s) * 1e3
