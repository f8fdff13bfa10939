"""Mean duration of the traced engine.readback spans (the host's wait for
the step's sampled ids), in ms."""
from harness import program_spans

read = program_spans.reader(program_spans.readback_wait_ms)
