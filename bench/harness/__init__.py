"""The benchmark's shared code: discovery of cells by name, set-up, the
drivers that time each kind of traffic, the trace reduction, the work counts,
the plain reference and the comparison that decides ``correct``."""
