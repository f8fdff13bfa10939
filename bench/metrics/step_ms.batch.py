"""Window over the engine steps that served at least one row, in ms."""
from harness import readers

read = readers.step_ms
