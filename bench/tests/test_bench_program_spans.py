"""The readers of the program's spans, on hand-made event lists, and the
read of a real (CPU) profile."""
from __future__ import annotations

import types

import pytest

import benchtree  # noqa: F401  (puts bench/ on the import path)
from harness import program_spans as ps
from harness import trace as tr

DEV, HOST, MAIN = "/device:TPU:0", "/host:CPU", "python3"
WINDOW = (0.0, 100e6)


def _ev(name, start_ms, dur_ms, plane=HOST, line=MAIN):
    return tr.Event(plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def _op(start_ms, dur_ms):
    return _ev("fusion", start_ms, dur_ms, DEV, tr.OP_LINE)


def _step(at, host_ms, read_ms):
    """An engine.step at ``at`` ms: host work, then its readback."""
    return [_ev("engine.step", at, host_ms + read_ms),
            _ev("engine.admit", at, 0.5),
            _ev("engine.dispatch", at + 0.5, host_ms - 1),
            _ev("engine.readback", at + host_ms - 0.5, read_ms),
            _ev("engine.finish", at + host_ms - 0.5 + read_ms, 0.5)]


def test_step_self_time_is_step_less_its_readback():
    evs = _step(10, 4, 20) + _step(40, 6, 30)
    # a readback on another thread is no child of these steps
    evs.append(_ev("engine.readback", 12, 1, line="other"))
    assert ps.step_host_ms(evs, WINDOW) == pytest.approx(5.0)
    assert ps.readback_wait_ms(evs, WINDOW) == pytest.approx(17.0)
    assert ps.probe_step_ms(evs, WINDOW) == pytest.approx(30.0)


def test_step_that_served_no_row_left_out():
    """A tick that admitted nothing from its queue is an engine.step with
    only its engine.admit: the step means leave it out."""
    evs = _step(10, 4, 20) + [_ev("engine.step", 40, 1),
                              _ev("engine.admit", 40, 1)]
    assert ps.step_host_ms(evs, WINDOW) == pytest.approx(4.0)
    assert ps.probe_step_ms(evs, WINDOW) == pytest.approx(24.0)
    assert ps.probe_step_ms(evs[5:], WINDOW) is None


def test_idle_attribution_with_overlapping_device_ops():
    evs = [_ev("engine.step", 10, 20),
           _ev("engine.readback", 15, 15),
           _ev("bench.step", 9, 22),
           _op(0, 8), _op(5, 7),      # union 0-12: idle 12-15 under the step
           _op(18, 12),               # idle 15-18 under the readback
           _op(28, 4),                # overlaps the last: union 18-32
           _op(40, 60)]               # idle 32-40, outside every engine span
    # idle: 3 (engine) + 3 (readback) + 8 (harness) = 14 ms
    assert ps.idle_in_engine_share(evs, WINDOW) == pytest.approx(300 / 14)
    # an op running past the window's edge is cut at it
    assert ps.idle_in_engine_share(evs, (0.0, 50e6)) == pytest.approx(300 / 14)
    assert ps.idle_in_engine_share(evs[3:], WINDOW) is None  # no engine span


def test_scaleout_init_spans_summed():
    evs = []
    for at, (a, w, p) in ((0, (2, 3, 40)), (500, (4, 5, 50))):
        evs += [_ev("archive.open", at, a), _ev("load.parse", at + 5, 1),
                _ev("engine.load_weights", at + 10, w),
                _ev("engine.init_pool", at + 300, p)]
    w = (0.0, 1000e6)
    assert ps.engine_init_ms(evs, w) == pytest.approx((45 + 59) / 2)


def test_spans_straddling_the_window_edge_left_out():
    evs = (_step(-5, 4, 20)     # starts before the window
           + _step(10, 4, 20)
           + _step(90, 2, 20))  # ends after it
    assert ps.step_host_ms(evs, WINDOW) == pytest.approx(4.0)
    assert ps.probe_step_ms(evs, WINDOW) == pytest.approx(24.0)
    # a scale-out whose archive.open lies before the window is not counted
    init = [_ev("archive.open", -3, 2), _ev("engine.load_weights", 2, 3),
            _ev("engine.init_pool", 10, 40),
            _ev("archive.open", 60, 2), _ev("engine.load_weights", 63, 3),
            _ev("engine.init_pool", 80, 30)]  # past the window's end
    assert ps.engine_init_ms(init, WINDOW) is None
    assert ps.engine_init_ms(init, (-10e6, 200e6)) == pytest.approx(40.0)


@pytest.mark.parametrize("reduce", [
    ps.step_host_ms, ps.readback_wait_ms, ps.probe_step_ms,
    ps.idle_in_engine_share, ps.engine_init_ms])
def test_none_without_program_spans(reduce):
    evs = [_op(0, 5), _op(10, 5), _ev("bench.step", 4, 8)]
    assert reduce(evs, WINDOW) is None
    assert reduce([], WINDOW) is None


def test_profile_read_finds_program_spans_on_the_cpu(tmp_path):
    """A real profile: the program's spans come back with their window,
    nested as they ran; a CPU profile has no device plane, so a run's
    readers give None rather than host times under a device metric."""
    import jax
    from jax.profiler import TraceAnnotation
    trace_dir = tmp_path / "bench_out" / "trace" / "tiny.batch"
    with jax.profiler.trace(str(trace_dir)):
        with TraceAnnotation("engine.step", rows=2):
            with TraceAnnotation("engine.readback"):
                jax.numpy.ones(8).block_until_ready()
        with TraceAnnotation("bench.other"):
            pass
    evs, window = ps.events(tr.find_xplane(str(trace_dir)))
    names = [e.name for e in evs]
    assert names.count("engine.step") == 1 and "bench.other" not in names
    step = ps.whole(evs, "engine.step", window)[0]
    read = ps.whole(evs, "engine.readback", window)[0]
    assert ps._inside(read, step)
    assert ps.step_host_ms(evs, window) is not None
    run = types.SimpleNamespace(cell=types.SimpleNamespace(
        bench=tmp_path / "bench", name="tiny.batch"))
    assert ps.traced(run) is None
    assert ps.reader(ps.step_host_ms)(run) is None
