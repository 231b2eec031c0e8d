"""R002/R003/R010 — purity, sync and donation discipline around traced code.

R002 (traced-purity, interprocedural): functions handed to ``jax.jit`` /
``shard_map`` / ``pallas_call`` (as calls or
decorators) run under tracing: side effects execute ONCE at trace time
and then silently never again — or, for Pallas interpret mode on CPU,
can crash the XLA compiler outright (an interpret kernel inside a CPU
mesh program, CLAUDE.md).  Flags ``print``, ``time.*``, ``random.*``/
``np.random.*``, ``open``/socket I/O, and global/nonlocal writes in the
traced function AND in every callee the summaries call graph can
attribute, across modules — a traced body outsourcing its side effect to
an imported helper is the same bug one hop away.  ``jax.debug.print`` /
``pl.debug_print`` are the sanctioned forms and stay silent.

R003 (host-sync-in-hot-loop): ``block_until_ready``/``jax.device_get``
inside a ``for``/``while`` loop in library code serializes the device
pipeline per iteration — the exact anti-pattern the fused ``lax.scan``
engine exists to avoid.  Deliberate syncs (stage-timing boundaries,
bounded-inflight backpressure) carry a noqa with their argument.

R010 (donated-buffer hygiene): ``donate_argnums`` lets XLA alias a
buffer input->output — which means XLA eventually FREES it.  Donating a
jax array that zero-copy aliases host numpy memory (``jnp.asarray`` of
an npz/numpy value, on CPU) corrupts the heap: XLA frees memory it never
allocated — the PR 5 resume incident (engine._load_state), observed as
nondeterministic segfaults under pytest.  Reading a name after passing
it to a donating call in the same scope is the softer cousin: the
buffer's contents are undefined.  Both are flagged; ``jnp.array(...,
copy=True)`` (owned memory) and rebinding the result are the sanctioned
shapes.  Aliased values are tracked through same-scope assignments and
one call-graph hop (a helper that RETURNS an aliased table taints its
callers' bindings — the exact _load_state -> run_stream shape).
"""

from __future__ import annotations

import ast

from locust_tpu.analysis.core import Finding, Rule, call_name, unparse


class TracedPurityRule(Rule):
    rule_id = "R002"
    title = "impure statement inside jit/shard_map/pallas-traced code"

    _MAX_DEPTH = 6

    def check_program(self, program):
        emitted: set[tuple] = set()
        for mod in program.modules.values():
            visited: set[int] = set()
            for expr in mod.traced_exprs:
                for fn in self._resolve_traced(program, mod, expr):
                    yield from self._visit(
                        program, fn, root=fn.name, chain=(fn.name,),
                        depth=0, visited=visited, emitted=emitted,
                    )

    def _resolve_traced(self, program, mod, expr):
        if isinstance(expr, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return mod.by_name.get(expr.name, [])
        if isinstance(expr, ast.Lambda):
            return [mod.lambda_summary(expr)]
        if isinstance(expr, ast.Name):
            return program.graph.resolve(mod, expr.id, include_nested=True)
        if isinstance(expr, ast.Attribute):
            return program.graph.resolve(
                mod, unparse(expr), include_nested=True
            )
        return []

    def _visit(self, program, fn, root, chain, depth, visited, emitted):
        if id(fn.node) in visited:
            return
        # A depth-truncated visit is not recorded — it never explored
        # its callees, and marking it would blind a later shallower path
        # (emitted dedups re-reported impurities; depth bounds recursion).
        if depth < self._MAX_DEPTH:
            visited.add(id(fn.node))
        for line, col, what in fn.impurities:
            key = (fn.rel, line, what)
            if key in emitted:
                continue
            emitted.add(key)
            if len(chain) == 1:
                where = f"inside traced function '{fn.name}'"
            else:
                where = (
                    f"inside '{fn.name}', reached from traced function "
                    f"'{root}' via {' -> '.join(chain)}"
                )
            yield Finding(
                self.rule_id, fn.rel, line, col,
                f"{what} {where}: runs once at trace time, then never "
                "again (or crashes the compiler in Pallas interpret "
                "mode) — hoist it out of the traced body",
            )
        if depth >= self._MAX_DEPTH:
            return
        for c in fn.calls:
            for callee in program.graph.resolve(fn.module, c.callee):
                if callee.node is fn.node:
                    continue
                yield from self._visit(
                    program, callee, root, chain + (callee.name,),
                    depth + 1, visited, emitted,
                )


_SYNC_ATTRS = {"block_until_ready"}
_SYNC_CALLS = {"jax.block_until_ready", "jax.device_get"}


class HostSyncInLoopRule(Rule):
    rule_id = "R003"
    title = "host sync inside a hot loop"

    def check_file(self, f, root):
        # Library code only: tests and scripts sync at will.
        top = f.rel.split("/", 1)[0]
        if top != "locust_tpu":
            return
        if "import jax" not in f.text:
            return
        seen: set[int] = set()  # nested loops: report each sync once
        for loop in ast.walk(f.tree):
            if not isinstance(loop, (ast.For, ast.While)):
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call) or id(node) in seen:
                    continue
                seen.add(id(node))
                callee = call_name(node)
                is_sync = callee in _SYNC_CALLS or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_ATTRS
                )
                if is_sync:
                    yield Finding(
                        self.rule_id,
                        f.rel,
                        node.lineno,
                        node.col_offset,
                        f"host sync ({callee}) inside a loop serializes "
                        "the device pipeline per iteration — batch the "
                        "loop into one dispatch (lax.scan) or noqa with "
                        "the backpressure/timing argument",
                    )


def _is_jnp_asarray(callee: str) -> bool:
    return callee in ("jnp.asarray", "jax.numpy.asarray") or (
        callee.endswith(".asarray") and callee.startswith(("jnp.", "jax."))
    )


def _is_uncopied_jnp_array(call: ast.Call, callee: str) -> bool:
    """``jnp.array(x, copy=False)`` — explicit no-copy is asarray in a
    trenchcoat.  Bare ``jnp.array`` copies by default and is safe."""
    if callee not in ("jnp.array", "jax.numpy.array"):
        return False
    for kw in call.keywords:
        if (
            kw.arg == "copy"
            and isinstance(kw.value, ast.Constant)
            and kw.value.value is False
        ):
            return True
    return False


def _body_stmts(node: ast.AST):
    """Child statements of a compound statement, in source order, not
    descending into nested function/class scopes."""
    for field in ("body", "orelse", "finalbody"):
        for stmt in getattr(node, field, []) or []:
            yield stmt
    for handler in getattr(node, "handlers", []) or []:
        yield from handler.body


def _own_exprs(stmt: ast.stmt):
    """Nodes belonging to THIS statement only: headers of compound
    statements (the ``with`` items, the ``if`` test, the ``for`` iter)
    but never child statements — those are walked in their own turn —
    and never nested function scopes."""
    stack = [
        child for child in ast.iter_child_nodes(stmt)
        if not isinstance(child, ast.stmt)
    ]
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(
            child for child in ast.iter_child_nodes(node)
            if not isinstance(child, ast.stmt)
        )


def _calls_in_stmt(stmt: ast.stmt):
    """Every Call in the statement's own expressions."""
    for node in _own_exprs(stmt):
        if isinstance(node, ast.Call):
            yield node


def _names_read(stmt: ast.stmt):
    for node in _own_exprs(stmt):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            yield node


class DonationHygieneRule(Rule):
    rule_id = "R010"
    title = "donated buffer aliases host memory or is read after donation"

    _MAX_DEPTH = 3

    def check_program(self, program):
        self._ret_memo: dict[int, set[int]] = {}
        self._in_progress: set[int] = set()
        for mod in program.modules.values():
            if not mod.donating:
                continue
            for fn in mod.functions:
                yield from self._scan_fn(program, mod, fn)

    # ------------------------------------------------------ alias tracking

    def _aliasing(self, program, mod, expr, aliased: set[str],
                  depth: int = 0) -> bool:
        """Does this expression (possibly) alias host numpy memory?"""
        if isinstance(expr, ast.Name):
            return expr.id in aliased
        if isinstance(expr, ast.Tuple):
            return any(
                self._aliasing(program, mod, e, aliased, depth)
                for e in expr.elts
            )
        if not isinstance(expr, ast.Call):
            return False
        callee = call_name(expr)
        if _is_jnp_asarray(callee) or _is_uncopied_jnp_array(expr, callee):
            return True
        args = list(expr.args) + [kw.value for kw in expr.keywords]
        # Constructor convention (KVBatch(...)): a capitalized bare name
        # wrapping an aliasing argument carries the alias.
        leaf = callee.split(".")[-1]
        if leaf[:1].isupper() and any(
            self._aliasing(program, mod, a, aliased, depth) for a in args
        ):
            return True
        if depth < self._MAX_DEPTH:
            for target in program.graph.resolve(mod, callee):
                if -1 in self._returns_aliased(program, target, depth + 1):
                    return True
        return False

    def _returns_aliased(self, program, fn, depth: int) -> set[int]:
        """Tuple indices (or -1 = the whole value) of ``fn``'s returns
        that may alias host numpy memory."""
        key = id(fn.node)
        if key in self._ret_memo:
            return self._ret_memo[key]
        if key in self._in_progress or depth > self._MAX_DEPTH:
            return set()
        self._in_progress.add(key)
        indices: set[int] = set()
        aliased: set[str] = set()

        def walk(stmts):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    continue
                if isinstance(stmt, ast.Assign):
                    self._track_assign(program, fn.module, stmt, aliased,
                                       depth)
                elif isinstance(stmt, ast.Return) and stmt.value is not None:
                    v = stmt.value
                    if isinstance(v, ast.Tuple):
                        for i, elt in enumerate(v.elts):
                            if self._aliasing(program, fn.module, elt,
                                              aliased, depth):
                                indices.add(i)
                    elif self._aliasing(program, fn.module, v, aliased,
                                        depth):
                        indices.add(-1)
                walk(list(_body_stmts(stmt)))

        body = fn.node.body
        walk(body if isinstance(body, list) else [])
        self._in_progress.discard(key)
        self._ret_memo[key] = indices
        return indices

    def _track_assign(self, program, mod, stmt: ast.Assign,
                      aliased: set[str], depth: int = 0) -> None:
        """Propagate aliasing through one assignment (rebinding kills)."""
        value = stmt.value
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                if self._aliasing(program, mod, value, aliased, depth):
                    aliased.add(t.id)
                else:
                    aliased.discard(t.id)
            elif isinstance(t, ast.Tuple) and all(
                isinstance(e, ast.Name) for e in t.elts
            ):
                taint: set[int] = set()
                if isinstance(value, ast.Tuple):
                    taint = {
                        i for i, e in enumerate(value.elts)
                        if self._aliasing(program, mod, e, aliased, depth)
                    }
                elif isinstance(value, ast.Call) and depth < self._MAX_DEPTH:
                    for target in program.graph.resolve(
                        mod, call_name(value)
                    ):
                        taint |= self._returns_aliased(
                            program, target, depth + 1
                        )
                for i, e in enumerate(t.elts):
                    if i in taint or -1 in taint:
                        aliased.add(e.id)
                    else:
                        aliased.discard(e.id)

    # ---------------------------------------------------------- the checks

    def _scan_fn(self, program, mod, fn):
        donating = mod.donating
        aliased: set[str] = set()
        donated: dict[str, tuple[str, int]] = {}  # name -> (callee, line)
        findings: list[Finding] = []

        def donate_positions(call: ast.Call) -> tuple[str, tuple[int, ...]]:
            callee = call_name(call)
            parts = callee.split(".")
            leaf = parts[-1]
            if leaf in donating and (
                len(parts) == 1 or parts[0] in ("self", "cls")
                or len(parts) == 2
            ):
                return callee, donating[leaf]
            return callee, ()

        def process(stmt: ast.stmt) -> None:
            # Reads of previously-donated names come first: the donation
            # mark only ever applies to LATER statements.
            for name in _names_read(stmt):
                hit = donated.get(name.id)
                if hit is not None:
                    callee, dline = hit
                    donated.pop(name.id)  # one finding per donation
                    findings.append(Finding(
                        self.rule_id, fn.rel, name.lineno, name.col_offset,
                        f"{name.id!r} is read after being donated to "
                        f"{callee}(...) on line {dline} — a donated "
                        "buffer's contents are undefined after the call; "
                        "use the call's result or copy before donating",
                    ))
            for call in _calls_in_stmt(stmt):
                callee, positions = donate_positions(call)
                for pos in positions:
                    if pos >= len(call.args):
                        continue
                    arg = call.args[pos]
                    if self._aliasing(program, mod, arg, aliased):
                        findings.append(Finding(
                            self.rule_id, fn.rel, call.lineno,
                            call.col_offset,
                            f"argument {pos} of donating call "
                            f"{callee}(...) may alias host numpy memory "
                            "(jnp.asarray keeps a zero-copy view on CPU) "
                            "— XLA frees donated buffers it then never "
                            "allocated, corrupting the heap (the PR 5 "
                            "resume incident); materialize with "
                            "jnp.array(..., copy=True) first",
                        ))
                    if isinstance(arg, ast.Name):
                        donated[arg.id] = (callee, call.lineno)
            if isinstance(stmt, ast.Assign):
                self._track_assign(program, mod, stmt, aliased)
                for t in stmt.targets:
                    for e in (
                        t.elts if isinstance(t, ast.Tuple) else [t]
                    ):
                        if isinstance(e, ast.Name):
                            donated.pop(e.id, None)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                if isinstance(stmt.target, ast.Name):
                    aliased.discard(stmt.target.id)
                    donated.pop(stmt.target.id, None)

        def walk(stmts):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef, ast.ClassDef)):
                    continue  # nested scopes get their own scan
                process(stmt)
                walk(list(_body_stmts(stmt)))

        body = fn.node.body
        walk(body if isinstance(body, list) else [])
        return findings
