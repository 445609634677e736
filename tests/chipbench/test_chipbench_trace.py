"""The reduction from a profiler trace to busy time, idle gaps and kernel
time: on a trace recorded here on the CPU, and on a hand-built device
plane laid out as a TPU trace is (``/device:TPU:0``, line ``XLA Ops``)."""
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from _tiny import ROOT  # noqa: F401  (puts the checkout on sys.path)
from chipbench.trace import WINDOW, Trace, merge


def test_merge_is_the_union():
    assert merge([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)]) == [
        (0, 3), (5, 9), (10, 11)]


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace")
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(path))
    with jax.profiler.TraceAnnotation(WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    return Trace.from_dir(str(path))


def test_cpu_trace_busy_and_gaps_fill_the_window(cpu_trace):
    tr = cpu_trace
    assert tr.devices, "XLA's ops on the CPU carry an hlo_op stat"
    busy = tr.busy_s()
    assert 0 < busy <= tr.window_s
    gaps = tr.idle_gaps()
    assert sum(s for _, s in gaps) + busy == pytest.approx(tr.window_s,
                                                          rel=1e-6)
    assert tr.kernel("dot_general")[0] == 3  # one matmul per call
    assert tr.kernel("no_such_op") == (0, 0.0)
    names = [n for n, _ in tr.top_ops(10)]
    assert any(n.startswith("dot_general") for n in names)
    bd = tr.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def _plane(name, lines):
    return NS(name=name, lines=[NS(name=n, events=e) for n, e in lines])


def test_device_plane_of_a_tpu_trace():
    host = _plane("/host:CPU", [("python3", [
        _ev(WINDOW, 1000, 9000), _ev("$numpy asarray", 5000, 2000)])])
    dev = _plane("/device:TPU:0", [
        ("XLA Ops", [_ev("%fusion.1 = f32[4] fusion()", 500, 1500),
                     _ev("%while.7 = (f32[4]) while()", 2500, 1500),
                     _ev("%divergence_sq.3 = f32[37,1] custom-call()",
                         2500, 1000),                    # inside the while
                     _ev("%fusion.2 = f32[37] fusion(%divergence_sq.3)",
                         3500, 500),
                     _ev("%fusion.3 = f32[4] fusion()", 8000, 4000)]),
        ("XLA Modules", [_ev("jit_run_block", 0, 20000)])])
    tr = Trace([host, dev])
    assert tr.window_s == pytest.approx(9e-6)
    # busy: [1000, 2000) + [2500, 4000) + [8000, 10000)
    assert tr.busy_s() == pytest.approx(4.5e-6)
    # only the kernel's own op, not the op that reads its output
    assert tr.kernel("divergence_sq") == (1, pytest.approx(1e-6))
    ops = dict(tr.top_ops())
    assert ops["while.7"] == pytest.approx(0.0)   # its body's ops are nested
    assert ops["fusion.3"] == pytest.approx(4e-6)
    gaps = tr.idle_gaps()
    assert gaps[0] == ("$numpy asarray", pytest.approx(4e-6))
    assert sum(s for _, s in gaps) == pytest.approx(4.5e-6)


def test_trace_without_the_window_span_is_refused():
    host = _plane("/host:CPU", [("python3", [_ev("other", 0, 10)])])
    with pytest.raises(ValueError):
        Trace([host])
