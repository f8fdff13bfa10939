"""Mean duration of the traced engine.step spans (each serves the probe at
bucket 1), in ms."""
from harness import program_spans

read = program_spans.reader(program_spans.probe_step_ms)
