"""Least time of the traced decode steps (max of FLOPs over peak and bytes
over bandwidth, counted from shapes and live lengths) over the device time
of the programs they ran, in %."""
from harness import readers

read = readers.roofline
