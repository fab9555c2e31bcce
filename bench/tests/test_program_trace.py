"""bench/program_trace.py on traces recorded on the CPU: the scoped ones of
``record_cpu_scoped_trace.py`` and the unscoped ``cpu_step`` of
``record_cpu_trace.py``."""
from pathlib import Path

import pytest

from bench import harness, program_trace, trace_reduce

DATA = Path(__file__).resolve().parent / "data"
SCOPED = {"cpu_t1_step": False, "cpu_hurst_step": True}   # -> projected
PHASES = ("pathsig.fwd", "pathsig.bwd", "pathsig.bwd.reconstruct",
          "pathsig.bwd.cotangent")


def reduce_fixture(name):
    """-> (trace_reduce's numbers, program_trace's) for one recording."""
    pd = trace_reduce._load(str(DATA / f"{name}.xplane.pb"))
    hlo = [(DATA / f"{name}.hlo.txt").read_text()]
    return (trace_reduce.reduce(pd, hlo_texts=hlo),
            program_trace.reduce(pd, hlo_texts=hlo))


@pytest.fixture(scope="module", params=sorted(SCOPED))
def scoped(request):
    return request.param, *reduce_fixture(request.param)


def test_program_scopes(scoped):
    """Each phase the program names holds device time; the reverse scan's
    halves lie inside its backward, which lies inside the transpose of the
    benchmark's scope, and forward and backward make up that scope."""
    name, red, prog = scoped
    sc = prog["program_scopes"]
    assert all(sc.get(p, 0) > 0 for p in PHASES), sc
    assert ("pathsig.proj.index" in sc) == SCOPED[name]
    tol = 1e-12
    assert (sc["pathsig.bwd.reconstruct"] + sc["pathsig.bwd.cotangent"]
            <= sc["pathsig.bwd"] + tol)
    assert sc["pathsig.bwd"] <= red["scope_bwd_s"] + tol
    assert (sc["pathsig.fwd"] + sc["pathsig.bwd"]
            <= red["scope_s"] + tol)
    assert sc["pathsig.fwd"] + sc["pathsig.bwd"] >= 0.95 * red["scope_s"]
    if SCOPED[name]:
        assert sc["pathsig.proj.index"] < (sc["pathsig.fwd"]
                                           + sc["pathsig.bwd"])


def test_unscoped_program_has_no_program_scopes():
    """A program that sets no scope of its own (the recording made before
    it did) reads an empty ``program_scopes``."""
    _, prog = reduce_fixture("cpu_step")
    assert prog["program_scopes"] == {}


@pytest.mark.parametrize("name", ["cpu_step"] + sorted(SCOPED))
def test_train_gaps_are_the_benchmarks_own(name):
    """A training step is called directly, under no span of the program,
    so naming gaps by the program's spans too leaves them as
    ``trace_reduce`` names them."""
    red, prog = reduce_fixture(name)
    assert prog["program_gaps"] == red["idle_gaps"]


def test_program_scope_attribution():
    """Every ``pathsig.*`` name on an op's path takes its self time,
    wherever in the path it is and however the transforms wrap it."""
    found = program_trace.PROGRAM_SCOPE.findall(
        "jit(step)/transpose(jvp(bench_sig))/pathsig.bwd/while/body/"
        "closed_call/pathsig.bwd.cotangent/"
        "transpose(jvp(pathsig.proj.index))/jit(_take)/scatter-add")
    assert found == ["pathsig.bwd", "pathsig.bwd.cotangent",
                     "pathsig.proj.index"]


def test_cli_refuses_without_a_tpu(capsys):
    """Like the harness, the command measures nothing off the chip."""
    cell = sorted(w["name"] for w in harness.load_json(
        harness.ROOT / "BENCHMARK.json")["workloads"])[0]
    assert program_trace.main(["--workload", cell, "--seed", "1"]) == 2
    assert "needs a TPU" in capsys.readouterr().err
