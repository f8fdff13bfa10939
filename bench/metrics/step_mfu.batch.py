"""Model FLOPs of every row-token the window processed over window x peak,
in %."""
from harness import readers

read = readers.mfu
