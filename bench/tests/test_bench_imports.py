"""Nothing model-independent in ``bench/`` names a model: a
configuration's model, reference, program config and counts are reached
only through its ``reference`` key (``harness.model_of``).  The BERT
files may be imported by the BERT module itself, by its counts and by
the MLM traffic sources, and by the tests of those files."""
import ast
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
MODEL_MODULES = {"bench.reference.bert_mlm", "bench.flops"}
MAY_IMPORT = {"reference/bert_mlm.py", "flops.py", "sources/pipeline.py",
              "sources/resident.py"}
FILES = sorted(str(p.relative_to(BENCH)) for p in BENCH.rglob("*.py")
               if p.parts[len(BENCH.parts)] != "tests")


def imported(path: Path):
    """Every module ``path`` imports, ``from a import b`` as ``a`` and
    ``a.b``."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            out.add(node.module)
            out.update(f"{node.module}.{a.name}" for a in node.names)
    return out


@pytest.mark.parametrize("name", FILES)
def test_no_model_is_imported_but_by_its_own_files(name):
    if name in MAY_IMPORT:
        return
    path = BENCH / name
    assert not imported(path) & MODEL_MODULES
    assert "bert" not in path.read_text().lower()


def test_the_harness_files_are_all_checked():
    want = {"harness.py", "run.py", "trace.py", "scopes.py", "control.py",
            "reference/core.py", "reference/__init__.py",
            "metrics/mfu.py", "metrics/matmul_roofline.py"}
    assert want <= set(FILES) - MAY_IMPORT
