"""R006/R007/R008 — environment and repo hygiene rules.

R006 (subprocess env hygiene): a chip belongs to ONE process at a time.
A child python spawned from tests/ or scripts/ with the inherited
environment initializes jax the way its parent did — on a machine with a
chip it tries to load the TPU library its parent holds and fails or
hangs — and imports whatever ``locust_tpu`` the ambient path finds
rather than this checkout's.  A spawn of python must pass ``env=`` built with BOTH ``JAX_PLATFORMS`` and
``PYTHONPATH`` pinned.  Heuristics: the command must visibly be python
(``sys.executable`` or a ``python`` literal in the argv expression, or a
local variable whose enclosing scope mentions ``sys.executable``); an
``env=`` forwarded from an enclosing function's parameter is trusted
(the wrapper's callers own the pinning).

R007 (bench contract): ``bench.py`` must print EXACTLY one JSON line on
stdout no matter what (the driver parses it).  Statically pinned as:
exactly one ``print(json.dumps(...))`` site, and every other ``print``
either goes to ``file=sys.stderr`` or is a flushed relay of an
already-captured JSON line (``flush=True``).

R008 (tracked artifact hygiene): ``__pycache__``/``*.pyc``/pytest caches
must never be tracked, and .gitignore must keep ignoring them.  Uses
``git ls-files`` (plain git, not python — R006 does not apply) and skips
silently when git is unavailable.
"""

from __future__ import annotations

import ast
import os
import re
import subprocess

from locust_tpu.analysis.core import Finding, Rule, call_name, unparse

_SPAWN_ATTRS = {"run", "Popen", "call", "check_call", "check_output"}
_REQUIRED_ENV = ("JAX_PLATFORMS", "PYTHONPATH")


def _enclosing_function(tree: ast.Module, node: ast.AST):
    """Innermost def containing ``node`` (None = module level)."""
    best = None
    for fn in ast.walk(tree):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (
                fn.lineno <= node.lineno
                and node.lineno <= max(
                    getattr(fn, "end_lineno", fn.lineno), fn.lineno
                )
                and (best is None or fn.lineno > best.lineno)
            ):
                best = fn
    return best


def _mentions_env_keys(scope: ast.AST) -> list[str]:
    """Which required env keys the scope visibly pins: string constants
    ("JAX_PLATFORMS": ...) or keyword names (env.update(PYTHONPATH=...))."""
    found = set()
    for node in ast.walk(scope):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            for key in _REQUIRED_ENV:
                if node.value == key:
                    found.add(key)
        elif isinstance(node, ast.keyword) and node.arg in _REQUIRED_ENV:
            found.add(node.arg)
    return [k for k in _REQUIRED_ENV if k in found]


def _is_python_spawn(call: ast.Call, scope: ast.AST) -> bool:
    if not call.args:
        return False
    argv = call.args[0]
    src = unparse(argv)
    if "sys.executable" in src or "python" in src.lower():
        return True
    if isinstance(argv, ast.Name) and scope is not None:
        return "sys.executable" in unparse(scope)
    return False


class SubprocessEnvRule(Rule):
    rule_id = "R006"
    title = "python child spawned without a pinned environment"

    def check_file(self, f, root):
        top = f.rel.split("/", 1)[0]
        if top not in ("tests", "scripts"):
            return
        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = call_name(node)
            leaf = callee.split(".")[-1]
            is_spawn = leaf == "Popen" or (
                leaf in _SPAWN_ATTRS and "subprocess" in callee
            )
            if not is_spawn:
                continue
            scope = _enclosing_function(f.tree, node) or f.tree
            if not _is_python_spawn(node, scope):
                continue
            env_kw = next(
                (kw for kw in node.keywords if kw.arg == "env"), None
            )
            if env_kw is None:
                yield Finding(
                    self.rule_id, f.rel, node.lineno, node.col_offset,
                    f"{callee} spawns python with the inherited "
                    "environment — the child would try to load the TPU "
                    "library its parent holds (one process per chip); "
                    "pass env= pinning JAX_PLATFORMS and PYTHONPATH",
                )
                continue
            # env forwarded from a wrapper's parameter: callers own it.
            if isinstance(env_kw.value, ast.Name) and isinstance(
                scope, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                params = {
                    a.arg
                    for a in (
                        scope.args.args
                        + scope.args.kwonlyargs
                        + scope.args.posonlyargs
                    )
                }
                if env_kw.value.id in params:
                    continue
            pinned = _mentions_env_keys(scope)
            missing = [k for k in _REQUIRED_ENV if k not in pinned]
            if missing:
                yield Finding(
                    self.rule_id, f.rel, node.lineno, node.col_offset,
                    f"{callee} spawns python with env= that never pins "
                    f"{' or '.join(missing)} in this scope — pin both so "
                    "the child stays off its parent's chip and imports "
                    "this checkout",
                )


class BenchContractRule(Rule):
    rule_id = "R007"
    title = "bench.py one-JSON-line contract"

    def check_file(self, f, root):
        if f.rel != "bench.py":
            return
        json_prints = []
        for node in ast.walk(f.tree):
            if not (
                isinstance(node, ast.Call) and call_name(node) == "print"
            ):
                continue
            kwargs = {kw.arg: kw for kw in node.keywords if kw.arg}
            is_json_dump = bool(node.args) and (
                isinstance(node.args[0], ast.Call)
                and call_name(node.args[0]).endswith("json.dumps")
            )
            if is_json_dump:
                json_prints.append(node)
                continue
            to_stderr = "file" in kwargs and unparse(
                kwargs["file"].value
            ).endswith("stderr")
            # A relay must print a CAPTURED value (a name or a subscript
            # like json_lines[-1]) — a flushed literal/f-string is still
            # stdout noise that breaks the one-line parse.
            flushed_relay = (
                "flush" in kwargs
                and isinstance(kwargs["flush"].value, ast.Constant)
                and kwargs["flush"].value.value is True
                and "file" not in kwargs
                and len(node.args) == 1
                and isinstance(node.args[0], (ast.Name, ast.Subscript))
            )
            if not to_stderr and not flushed_relay:
                yield Finding(
                    self.rule_id, f.rel, node.lineno, node.col_offset,
                    "print to stdout outside the one-JSON-line contract — "
                    "route diagnostics to file=sys.stderr (the driver "
                    "parses stdout as a single JSON line)",
                )
        if len(json_prints) != 1:
            where = json_prints[1] if len(json_prints) > 1 else None
            yield Finding(
                self.rule_id, f.rel,
                where.lineno if where is not None else 1,
                where.col_offset if where is not None else 0,
                f"bench.py must have exactly ONE print(json.dumps(...)) "
                f"emission site, found {len(json_prints)} — the driver "
                "contract is one JSON line from one place (emit())",
            )


_TRACKED_JUNK = re.compile(
    r"(^|/)__pycache__(/|$)|\.py[co]$|(^|/)\.pytest_cache(/|$)"
    r"|(^|/)\.hypothesis(/|$)|(^|/)\.DS_Store$"
)
_IGNORE_WANTED = ("__pycache__/", "*.pyc")


class TrackedArtifactRule(Rule):
    rule_id = "R008"
    title = "build/cache artifacts tracked by git"

    def check_project(self, files, root):
        if not os.path.isdir(os.path.join(root, ".git")):
            return  # fixture trees / exported sources: nothing to check
        try:
            out = subprocess.run(
                ["git", "-C", root, "ls-files"],
                capture_output=True, text=True, timeout=30,
            )
        except (OSError, subprocess.SubprocessError):
            return
        if out.returncode != 0:
            return
        for tracked in out.stdout.splitlines():
            if _TRACKED_JUNK.search(tracked):
                yield Finding(
                    self.rule_id, tracked, 1, 0,
                    "build/cache artifact is tracked by git — "
                    "`git rm -r --cached` it (and keep .gitignore "
                    "covering it)",
                )
        gi_path = os.path.join(root, ".gitignore")
        try:
            with open(gi_path, encoding="utf-8") as fh:
                entries = {ln.strip() for ln in fh}
        except OSError:
            entries = set()
        for want in _IGNORE_WANTED:
            if want not in entries:
                yield Finding(
                    self.rule_id, ".gitignore", 1, 0,
                    f".gitignore is missing {want!r} — cache artifacts "
                    "will show up as untracked noise and eventually get "
                    "committed",
                )
