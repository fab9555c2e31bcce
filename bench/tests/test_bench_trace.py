"""bench/trace_reduce.py on a trace recorded on the CPU
(``record_cpu_trace.py``): three training steps with the signature under
the benchmark's scope."""
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduced():
    pd = trace_reduce._load(str(DATA / "cpu_step.xplane.pb"))
    hlo = (DATA / "cpu_step.hlo.txt").read_text()
    return trace_reduce.reduce(pd, hlo_texts=[hlo])


def test_window_and_busy(reduced):
    assert reduced["n_devices"] == 1
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] <= reduced["op_s"] * (1 + 1e-9)


def test_scope_time(reduced):
    """The signature's scope holds forward and backward; the backward is
    its transpose, a part of it; nothing here is a collective."""
    assert 0 < reduced["scope_bwd_s"] < reduced["scope_s"] < reduced["op_s"]
    assert reduced["collective_s"] == 0


def test_breakdown(reduced):
    ops = reduced["device_ops"]
    assert 1 <= len(ops) <= 10
    assert all(isinstance(n, str) and v > 0 for n, v in ops)
    assert [v for _, v in ops] == sorted((v for _, v in ops), reverse=True)
    assert any("transpose(jvp(bench_sig))" in n for n, _ in ops)
    gaps = reduced["idle_gaps"]
    assert 1 <= len(gaps) <= 10
    assert {n for n, _ in gaps} <= {"bench.window", "bench.dispatch",
                                    "bench.block"}
    idle = reduced["window_s"] - reduced["busy_s"]
    assert abs(sum(v for _, v in gaps) - idle) <= 1e-6 * reduced["window_s"]


def test_op_names_from_hlo():
    text = ('HloModule jit_step\n'
            '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(step)/transpose(jvp(bench_sig))/mul"}\n'
            '  ROOT all-reduce.1 = f32[8]{0} all-reduce(%x), '
            'metadata={op_name="jit(step)/psum"}\n')
    names = trace_reduce.hlo_op_names([text])
    assert names == {"fusion.3": "jit(step)/transpose(jvp(bench_sig))/mul",
                     "all-reduce.1": "jit(step)/psum"}


def test_no_window_is_an_error():
    class Empty:
        planes = []
    with pytest.raises(ValueError):
        trace_reduce.reduce(Empty())
