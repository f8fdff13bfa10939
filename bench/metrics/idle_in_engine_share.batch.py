"""Share of the device's idle time in the traced window that falls under
an engine.* span other than engine.readback, in %."""
from harness import program_spans

read = program_spans.reader(program_spans.idle_in_engine_share)
