"""What the scripts import and what the documents name is there.

Pure ``ast`` and text, no JAX: a tool that imports a sibling under
``tools/`` names a file that exists and a top-level name defined in it, any
other top-level module it imports can be found without ``tools/`` on the
path, and every script, config or module path a living document puts
between back-ticks exists. Deleting a helper that a surviving tool still
imports, or a script a document still tells the reader to run, fails here."""

import ast
import functools
import glob
import importlib.machinery
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
PACKAGE = os.path.join(ROOT, "deeplearning_tpu")

SCRIPTS = sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(TOOLS, "*.py"))
    + [os.path.join(ROOT, "chip_smoke.py"),
       os.path.join(ROOT, "__graft_entry__.py")])
DOCUMENTS = ("README.md", os.path.join(".claude", "skills", "verify",
                                       "SKILL.md"))


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read(), filename=path)


@functools.lru_cache(maxsize=None)
def top_level_names(path):
    """Names the module at ``path`` binds at its top level, conditional
    and guarded blocks (``if``, ``try``, ``with``) included."""
    tree = _parse(path)
    names = set()

    def visit(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                names.add(node.name)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for a in node.names:
                    names.add((a.asname or a.name).split(".")[0])
            elif isinstance(node, (ast.Assign, ast.AnnAssign,
                                   ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    names.update(n.id for n in ast.walk(t)
                                 if isinstance(n, ast.Name))
            elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For,
                                   ast.While)):
                visit(node.body)
                visit(getattr(node, "orelse", []))
                visit(getattr(node, "finalbody", []))
                for h in getattr(node, "handlers", []):
                    visit(h.body)
    visit(tree.body)
    return names


def _found_without_tools(name):
    if name in sys.stdlib_module_names:
        return True
    if os.path.exists(os.path.join(ROOT, name)) or \
            os.path.exists(os.path.join(ROOT, name + ".py")):
        return True
    path = [p for p in sys.path
            if os.path.abspath(p or os.getcwd()) != TOOLS]
    return importlib.machinery.PathFinder.find_spec(name, path) is not None


def wiring_faults(script):
    faults = []
    for node in ast.walk(_parse(os.path.join(ROOT, script))):
        if isinstance(node, ast.Import):
            wanted = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            wanted = [(node.module, a.name) for a in node.names]
        else:
            continue
        for module, name in wanted:
            head = module.split(".")[0]
            sibling = os.path.join(TOOLS, head + ".py")
            if not os.path.exists(sibling):
                if not _found_without_tools(head):
                    faults.append(f"line {node.lineno}: module {head!r} is "
                                  "neither under tools/ nor installed")
            elif name not in (None, "*") and \
                    name not in top_level_names(sibling):
                faults.append(f"line {node.lineno}: tools/{head}.py "
                              f"defines no top-level {name!r}")
    return faults


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_imports_resolve(script):
    assert wiring_faults(script) == []


def test_a_missing_sibling_and_a_missing_name_are_faults(tmp_path):
    """The check itself, on a planted script: what it is for."""
    planted = tmp_path / "planted.py"
    planted.write_text(
        "import json\n"
        "from loadgen import make_images, no_such_name\n"
        "def f():\n"
        "    from no_such_helper_module import thing\n")
    faults = wiring_faults(str(planted))
    assert len(faults) == 2
    assert "no_such_name" in faults[0] and "no_such_helper_module" in faults[1]


_PATH = re.compile(r"(?<![\w/.~-])([\w./-]+\.(?:py|yaml))\b")


def named_paths(text):
    """Every ``*.py`` / ``*.yaml`` path inside back-ticks: inline spans and
    fenced blocks alike."""
    quoted = re.findall(r"```.*?```", text, flags=re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text,
                                               flags=re.S))
    return sorted({m.group(1) for chunk in quoted + inline
                   for m in _PATH.finditer(chunk)})


def _exists(path, package_basenames):
    """As written from the repo's root; or, the documents' short form, from
    the package's root (``obs/spans.py``); or a bare file name of a root
    script, a tool or a module of the package (``trainer.py``)."""
    if os.path.exists(os.path.join(ROOT, path)) or \
            os.path.exists(os.path.join(PACKAGE, path)):
        return True
    return "/" not in path and (
        os.path.exists(os.path.join(TOOLS, path))
        or path in package_basenames)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    with open(os.path.join(ROOT, document)) as f:
        paths = named_paths(f.read())
    assert paths, "the pattern found no path at all"
    basenames = {os.path.basename(p) for p in glob.glob(
        os.path.join(PACKAGE, "**", "*.py"), recursive=True)}
    missing = [p for p in paths if not _exists(p, basenames)]
    assert missing == []
