"""The verify layer's shape (PR 44): three modules with the arrows one way —
``ops/verifier.py`` -> ``ops/programs.py`` -> the kernel it is handed;
``ops/verifier.py`` -> ``ops/ed25519.py`` -> ``fe`` / ``ref25519`` — and no
choice of program left to the environment.  Source checks only: nothing here
imports JAX.
"""

from __future__ import annotations

import ast
import glob
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS = os.path.join(REPO, "stellar_tpu", "ops")
# a path: deployment (ops/__init__.py)
ALLOWED_ENV = {"JAX_COMPILATION_CACHE_DIR"}
ENV_FILES = sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(OPS, "*.py"))
) + ["stellar_tpu/main/config.py"]


def tree_of(relpath: str) -> ast.AST:
    with open(os.path.join(REPO, relpath)) as f:
        return ast.parse(f.read(), relpath)


def is_os_env(node) -> bool:
    return (
        isinstance(node, ast.Attribute)
        and node.attr in ("environ", "environb", "getenv")
        and isinstance(node.value, ast.Name)
        and node.value.id == "os"
    )


def env_reads(tree: ast.AST):
    """-> (every reference to the process environment, the names read by
    those of them that name a variable outright)."""
    refs, named = 0, []
    for node in ast.walk(tree):
        if is_os_env(node):
            refs += 1
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            refs += sum(a.name in ("environ", "environb", "getenv") for a in node.names)
        key = None
        if isinstance(node, ast.Call) and node.args:
            f = node.func
            if is_os_env(f) or (isinstance(f, ast.Attribute) and f.attr == "get" and is_os_env(f.value)):
                key = node.args[0]
        elif isinstance(node, ast.Subscript) and is_os_env(node.value):
            key = node.slice
        if isinstance(key, ast.Constant):
            named.append(key.value)
    return refs, named


@pytest.mark.parametrize("relpath", ENV_FILES)
def test_no_choice_of_program_is_read_from_the_environment(relpath):
    refs, named = env_reads(tree_of(relpath))
    assert set(named) <= ALLOWED_ENV, named
    # and no reference that hides the name it reads
    assert refs == len(named), (refs, named)


def test_the_env_walker_sees_a_read_when_there_is_one():
    src = "import os\na = os.environ.get('X', '1')\nb = os.getenv('Y')\nc = os.environ['Z']\nd = os.environ\n"
    refs, named = env_reads(ast.parse(src))
    assert (refs, sorted(named)) == (4, ["X", "Y", "Z"])


def imported(relpath: str) -> set:
    """Every module a file imports, at any depth of nesting: absolute names
    whole, relative ones by their last parts (``from . import a`` -> a)."""
    out = set()
    for node in ast.walk(tree_of(relpath)):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.module:
                out.add(node.module)
            if node.level and not node.module:
                out.update(a.name for a in node.names)
            elif node.module:
                out.update(node.module + "." + a.name for a in node.names)
    return out


ARROWS = {
    # the kernel: what a program's body is traced through, no host pipeline
    "stellar_tpu/ops/ed25519.py": ("programs", "verifier", "threading", "concurrent", "compile_events"),
    # the books know the kernel they were handed, not who handed it
    "stellar_tpu/ops/programs.py": ("verifier", "ed25519"),
}


@pytest.mark.parametrize("relpath", ARROWS)
def test_the_arrows_point_one_way(relpath):
    names = imported(relpath)
    for banned in ARROWS[relpath]:
        hits = [n for n in names if banned in n.split(".")]
        assert not hits, (relpath, "imports", hits)


def test_kernel_module_stays_small_and_no_ops_module_outgrows_its_box():
    lines = {os.path.basename(p): sum(1 for _ in open(p)) for p in glob.glob(os.path.join(OPS, "*.py"))}
    assert lines["ed25519.py"] <= 400, lines
    assert max(lines.values()) <= 900, lines
