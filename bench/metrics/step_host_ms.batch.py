"""Mean over the traced engine.step spans of their duration less their
engine.readback child: the program's host work each step, in ms."""
from harness import program_spans

read = program_spans.reader(program_spans.step_host_ms)
