"""Mean over the traced scale-outs of archive.open + engine.load_weights +
engine.init_pool, the set-up outside LOAD's critical path, in ms."""
from harness import program_spans

read = program_spans.reader(program_spans.engine_init_ms)
