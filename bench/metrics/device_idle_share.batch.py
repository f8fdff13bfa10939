"""1 - union of device-op intervals / traced window, in %."""
from harness import readers

read = readers.idle_share
