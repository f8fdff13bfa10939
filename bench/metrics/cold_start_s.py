"""Mean seconds of a scale-out: open the archive, build an engine, LOAD,
submit the probe and get its first token; summed time over count."""


def read(run):
    s = run.window.scaleouts
    return sum(x.cold_s for x in s) / len(s) if s else None
