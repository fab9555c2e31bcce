"""The repository's shape: which layers import which, and that the README
and CI name only files and modules that exist.

1. *Layer arrows*: ``repro.obs`` is the bottom layer and imports nothing
   else of ``repro``; ``core`` adds only ``obs``; ``distributed`` stands
   alone; ``kernels`` sits on all three.  Each import runs in a fresh
   interpreter, so what one test loaded cannot hide another's arrow.
2. *Documents*: every backticked path in ``README.md`` resolves, and every
   ``python -m <module>`` / ``python <file>`` in the CI workflow names
   something that exists.
"""
import importlib.util
import os
import pathlib
import re
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
SRC = ROOT / "src"

# package -> the repro packages importing it may load (itself included)
LAYERS = {
    "obs": {"obs"},
    "core": {"core", "obs"},
    "distributed": {"distributed"},
    "kernels": {"kernels", "core", "distributed", "obs"},
}


@pytest.mark.parametrize("pkg", sorted(LAYERS),
                         ids=[f"repro.{p}" for p in sorted(LAYERS)])
def test_layer_imports_point_down(pkg):
    code = (f"import sys, repro.{pkg}\n"
            "print(' '.join(sorted({n.split('.')[1] for n in sys.modules\n"
            "                        if n.startswith('repro.')})))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        filter(None, [str(SRC), os.environ.get("PYTHONPATH")])))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=str(ROOT), timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    loaded = set(out.stdout.split())
    assert pkg in loaded
    assert loaded <= LAYERS[pkg], (
        f"import repro.{pkg} loads {sorted(loaded - LAYERS[pkg])}, above "
        f"its layer")


def _inline_code(markdown: str) -> list[str]:
    """Single-backtick spans outside fenced blocks."""
    prose = re.sub(r"```.*?```", "", markdown, flags=re.S)
    return re.findall(r"`([^`\n]+)`", prose)


def test_readme_names_only_paths_that_exist():
    paths = [span for span in _inline_code((ROOT / "README.md").read_text())
             if re.fullmatch(r"[\w.-]*/[\w./-]*\.\w+", span)]
    assert paths, "no paths found: the pattern no longer matches the README"
    missing = [p for p in paths
               if not (ROOT / p).exists() and not (SRC / "repro" / p).exists()]
    assert not missing, f"README names paths that do not exist: {missing}"


def _find_module(name: str):
    try:
        return importlib.util.find_spec(name)
    except ModuleNotFoundError:          # a missing parent package
        return None


def test_ci_runs_only_modules_that_exist(monkeypatch):
    text = (ROOT / ".github" / "workflows" / "ci.yml").read_text()
    modules = re.findall(r"\bpython3?\s+-m\s+([\w.]+)", text)
    files = re.findall(r"\bpython3?\s+(?!-)(\S+\.py)\b", text)
    assert "pytest" in modules and files
    monkeypatch.syspath_prepend(str(SRC))
    monkeypatch.syspath_prepend(str(ROOT))
    missing = [m for m in modules if _find_module(m) is None]
    missing += [f for f in files if not (ROOT / f).is_file()]
    assert not missing, f"CI runs modules or files that do not exist: {missing}"
