"""The package's layers, held by its import graph: which part of
``horovod_tpu`` may not import which. Read with ``ast`` from the source
(lazy imports inside functions count; nothing is imported or run). The
same nine lines are the legend of ``docs/ARCHITECTURE.md`` section 8."""

import ast
import os

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = "horovod_tpu"

# (rule, the part held: package-relative directories or modules,
#  what it may not import: package-relative names, top-level modules)
RULES = [
    ("ops imports nothing of models, serving, parallel, optimizer, elastic",
     ["ops/"],
     ["models", "serving", "parallel", "optimizer", "optimizer_sharded",
      "elastic"], []),
    ("models imports nothing of serving", ["models/"], ["serving"], []),
    ("parallel imports nothing of serving", ["parallel/"], ["serving"], []),
    ("the trainer core imports nothing of serving or models",
     ["core.py", "spmd.py", "collective.py", "fusion.py", "overlap.py",
      "optimizer.py", "compression.py", "process_set.py"],
     ["serving", "models"], []),
    ("tracing imports nothing of the package but metrics",
     ["tracing.py"], ["*"], []),          # "*": any module not in ALLOWED
    ("elastic and runner import nothing of models or serving",
     ["elastic/", "runner/"], ["models", "serving"], []),
    ("data and utils import nothing of models or serving",
     ["data/", "utils/"], ["models", "serving"], []),
    ("serving imports no frontend: torch, tensorflow, spark, ray, lightning",
     ["serving/"], ["torch", "tensorflow", "spark", "ray", "lightning"],
     ["torch", "tensorflow", "pyspark", "ray", "lightning",
      "pytorch_lightning"]),
    ("nothing in the package imports benchmark, tools, bench or chip_smoke",
     [""], [], ["benchmark", "tools", "bench", "chip_smoke"]),
]

# (file, imported module): what a rule lets through, with the reason.
ALLOWED = {
    ("tracing.py", "horovod_tpu.metrics"):
        "the rule's own exception: the counters the spans feed live in "
        "the metrics registry",
    ("tracing.py", "horovod_tpu.timeline"):
        "the one crossing at PR 29: phase() writes the NEGOTIATE / QUEUE / "
        "EXEC rows of an eager collective into the active timeline (lazy; "
        "a no-op without one). It goes when those rows move to timeline.py",
}


def _sources(part):
    root = os.path.join(_REPO, _PKG)
    path = os.path.join(root, part)
    if part.endswith(".py"):
        return [path]
    found = []
    for here, _, names in os.walk(path):
        found += [os.path.join(here, n) for n in names if n.endswith(".py")]
    assert found, part
    return sorted(found)


def _imports(path):
    """Every module ``path`` imports, absolute: ``from . import x`` and
    ``from horovod_tpu import x`` name ``horovod_tpu.x`` (a submodule or a
    name of the package: either way what the file reaches for)."""
    package = os.path.relpath(path, _REPO).split(os.sep)[:-1]
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, node.lineno
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - (node.level - 1)]
                base = ".".join(up + ([base] if base else []))
            for a in node.names:
                yield f"{base}.{a.name}", node.lineno
            yield base, node.lineno


@pytest.mark.parametrize("rule, held, inner, outer", RULES,
                         ids=[r[0] for r in RULES])
def test_the_arrows_point_one_way(rule, held, inner, outer):
    crossings = []
    for part in held:
        for path in _sources(part):
            name = os.path.relpath(path, os.path.join(_REPO, _PKG))
            for mod, line in _imports(path):
                parts = mod.split(".")
                if parts[0] == _PKG and len(parts) > 1:
                    bad = parts[1] in inner or "*" in inner
                else:
                    bad = parts[0] in outer
                if bad and (name, ".".join(parts[:2])) not in ALLOWED:
                    crossings.append(f"{_PKG}/{name}:{line} imports {mod}")
    assert not crossings, f"{rule}:\n" + "\n".join(sorted(set(crossings)))


def test_every_allowed_crossing_still_exists():
    for (name, mod), why in ALLOWED.items():
        path = os.path.join(_REPO, _PKG, name)
        assert any(m == mod or m.startswith(mod + ".")
                   for m, _ in _imports(path)), (name, mod, why)
