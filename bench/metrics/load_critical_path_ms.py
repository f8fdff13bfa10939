"""Mean LoadReport.critical_path_s of the window's scale-outs, in ms."""


def read(run):
    s = run.window.scaleouts
    return sum(x.critical_path_s for x in s) / len(s) * 1e3 if s else None
