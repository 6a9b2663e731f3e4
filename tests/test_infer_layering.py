"""``infer/``'s imports point one way (ISSUE 30):

    ops <- decode (block + forward) <- paged (pool, views, paged programs)
        <- speculative <- executor <- scheduler <- serve

with ``afmoe_serve`` (another architecture's block over the paged view)
above ``paged`` and below ``executor``.  Every ``import`` of the five
modules is collected with ``ast`` at any depth — an import inside a
function body is an arrow like any other — and none may point up.  The
one arrow left is ``decode._forward`` reaching ``afmoe_serve`` for the
architecture a preset's type selects (ROADMAP C1 / C12)."""

import ast
import os

import paddle_operator_tpu.infer as infer

PKG = "paddle_operator_tpu.infer"
# module -> the modules of infer/ it may not name, directly or in a body
FORBIDDEN = {
    "decode": {"paged", "speculative", "executor", "scheduler", "serve"},
    "paged": {"speculative", "executor", "scheduler", "serve",
              "afmoe_serve"},
    "speculative": {"executor", "scheduler", "serve", "afmoe_serve"},
    "afmoe_serve": {"executor", "scheduler", "serve", "speculative"},
    "executor": {"scheduler", "serve"},
}


def _imports(module: str) -> set:
    """Every module of ``infer/`` that ``infer/<module>.py`` imports."""
    path = os.path.join(os.path.dirname(infer.__file__), module + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    named = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            names = [node.module] + [f"{node.module}.{a.name}"
                                     for a in node.names]
        elif isinstance(node, ast.ImportFrom):      # relative: from . import x
            base = PKG if not node.module else f"{PKG}.{node.module}"
            names = [base] + [f"{base}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            if name.startswith(PKG + "."):
                named.add(name[len(PKG) + 1:].split(".")[0])
    return named


def test_infer_imports_point_one_way():
    up = {m: sorted(_imports(m) & banned) for m, banned in FORBIDDEN.items()}
    assert not any(up.values()), (
        f"imports that point up the stack (at any depth): "
        f"{ {m: v for m, v in up.items() if v} }")
    assert not os.path.exists(os.path.join(
        os.path.dirname(infer.__file__), "batcher.py"))
    # what is left, and named in ROADMAP: the architecture dispatch
    assert _imports("decode") <= {"qos", "afmoe_serve"}
