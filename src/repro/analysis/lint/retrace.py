"""retrace-hazard checker (RT*): the static twin of the warmup
``--max-decode-compiles 0`` gate.

``ServeEngine.warmup()`` front-loads every (bucket, lanes) compile so
steady state never retraces (PR6). The constructs that silently defeat
that are flagged here:

  RT001  ``jax.jit`` called inside a loop — builds a fresh cache entry per
         iteration; hoist to ``__init__``/module scope
  RT002  ``static_argnames``/``static_argnums`` marking an array-valued
         param static — every distinct array retraces (and unhashable
         values raise at call time)
  RT003  iterating a ``set`` while building traced structures — set order
         is salted per process, so pytree/leaf order differs across runs
         and across processes (dict/pytree construction must be
         deterministic)
  RT004  Python ``if``/``while`` testing a ``jnp.``/``jax.`` expression —
         under trace this either raises ConcretizationTypeError or forces
         a sync + retrace per branch
  RT005  a ``Mesh`` constructed inside a jitted function that also issues
         collectives (shard_map/psum/...) — the mesh is a trace-time
         constant, so every distinct device assignment retraces, and
         closing over it defeats the one-trace decode contract
         (DESIGN.md §15: meshes are built at engine/plan build time and
         passed in)

Scope: ``core/`` and all of ``serve/`` (the policy resolver and engine are
where plans and pytrees are built); RT005 additionally covers
``backends/``, ``distributed/`` and ``kernels/`` (where shard_map lives).
"""
from __future__ import annotations

import ast
import re
from typing import List, Optional

from repro.analysis.lint.core import Checker, Finding, Rule, register_checker

RT001 = Rule("RT001", "jax.jit inside a loop — one compile cache entry per "
                      "iteration; hoist it")
RT002 = Rule("RT002", "array-valued parameter marked as a jit static arg "
                      "— retraces per distinct value, unhashable at call")
RT003 = Rule("RT003", "iteration over a set while building pytrees — "
                      "nondeterministic order breaks trace stability")
RT004 = Rule("RT004", "Python control flow on a traced (jnp/jax) value — "
                      "concretization error or per-branch retrace")
RT005 = Rule("RT005", "collective (shard_map/psum/...) closing over a Mesh "
                      "built inside a jitted function — retraces per device "
                      "assignment; hoist mesh construction to build time")

# params that hold arrays/pytrees in this codebase's signatures
_ARRAYISH = re.compile(
    r"^(params|tokens|toks|pool|logits|key|batch|x|q|k|v|kv|pt|lengths|"
    r"write_pos|cache|caches|state|latents|scores|mask|bias)$")

_TRACED_ROOT = re.compile(r"^(jnp|jax|lax)\.")


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


@register_checker
class RetraceChecker(Checker):
    rules = (RT001, RT002, RT003, RT004)

    def applies(self, path: str) -> bool:
        return bool(re.search(r"(^|/)(core|serve)(/|/.*/)[^/]*\.py$", path))

    def check(self, path: str, tree: ast.Module,
              source: str) -> List[Finding]:
        lines = source.splitlines()
        findings: List[Finding] = []

        def visit(node: ast.AST, loop_depth: int) -> None:
            for child in ast.iter_child_nodes(node):
                depth = loop_depth
                if isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    depth += 1
                    findings.extend(self._iter_target(child, path, lines))
                    if isinstance(child, ast.While):
                        findings.extend(
                            self._traced_test(child.test, path, lines))
                if isinstance(child, ast.If):
                    findings.extend(
                        self._traced_test(child.test, path, lines))
                if isinstance(child, ast.Call):
                    d = _dotted(child.func) or ""
                    if d in ("jax.jit", "jit") and depth > 0:
                        findings.append(self.finding(
                            RT001.id, path, child,
                            "jax.jit in a loop allocates a new compiled "
                            "function per iteration — hoist to build time",
                            lines))
                    findings.extend(self._static_args(child, d, path, lines))
                visit(child, depth)

        visit(tree, 0)
        return findings

    def _iter_target(self, loop: ast.AST, path: str,
                     lines) -> List[Finding]:
        it = getattr(loop, "iter", None)
        if it is None:
            return []
        is_set = isinstance(it, ast.Set) or (
            isinstance(it, ast.Call) and (_dotted(it.func) or "") == "set")
        if is_set:
            return [self.finding(
                RT003.id, path, it,
                "set iteration order is salted per process — sort it "
                "(`sorted(...)`) before building traced structures", lines)]
        return []

    def _traced_test(self, test: ast.AST, path: str,
                     lines) -> List[Finding]:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Call):
                d = _dotted(sub.func) or ""
                if _TRACED_ROOT.match(d):
                    return [self.finding(
                        RT004.id, path, test,
                        f"`{ast.unparse(test)}` branches Python control "
                        "flow on a traced value — use jnp.where/lax.cond "
                        "or hoist the decision to build time", lines)]
        return []

    def _static_args(self, call: ast.Call, dotted: str, path: str,
                     lines) -> List[Finding]:
        if dotted.rsplit(".", 1)[-1] not in ("jit", "pjit"):
            return []
        out: List[Finding] = []
        for kw in call.keywords:
            if kw.arg not in ("static_argnames", "static_argnums"):
                continue
            names: List[str] = []
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and \
                        isinstance(sub.value, str):
                    names.append(sub.value)
            if kw.arg == "static_argnums" and not names:
                # positional statics: resolve through the jitted function's
                # signature when it is an inline lambda/def we can see
                names.extend(self._positional_names(call, kw.value))
            for name in names:
                if _ARRAYISH.match(name):
                    out.append(self.finding(
                        RT002.id, path, kw.value,
                        f"`{name}` marked static — arrays are unhashable "
                        "and every distinct value would retrace", lines))
        return out

    @staticmethod
    def _positional_names(call: ast.Call, numsval: ast.AST) -> List[str]:
        if not call.args or not isinstance(call.args[0], ast.Lambda):
            return []
        lam = call.args[0]
        params = [a.arg for a in lam.args.args]
        nums = [s.value for s in ast.walk(numsval)
                if isinstance(s, ast.Constant) and isinstance(s.value, int)]
        return [params[i] for i in nums if 0 <= i < len(params)]


# collective entry points whose closure would capture the in-trace mesh
_COLLECTIVES = frozenset({
    "shard_map", "psum", "pmax", "pmin", "pmean", "all_gather", "ppermute",
    "all_to_all", "psum_scatter", "axis_index",
})
# mesh constructors (suffix-matched: jax.sharding.Mesh, sharding.make_mesh,
# launch.mesh.make_host_mesh all count)
_MESH_CTORS = frozenset({"Mesh", "make_mesh", "make_host_mesh"})


@register_checker
class MeshRetraceChecker(Checker):
    """RT005 — the shard_map twin of RT001: mesh construction belongs at
    build time (engine __init__ / plan resolution), never inside a traced
    function. A Mesh is hashed into the jit cache key, so building one
    per call silently defeats the warmup one-trace guarantee, and under
    `jit(shard_map(...))` the inner mesh must match the outer sharding
    anyway — there is no legitimate reason to construct it in-trace."""

    rules = (RT005,)

    def applies(self, path: str) -> bool:
        return bool(re.search(
            r"(^|/)(core|serve|backends|distributed|kernels)(/|/.*/)[^/]*\.py$",
            path))

    def check(self, path: str, tree: ast.Module,
              source: str) -> List[Finding]:
        lines = source.splitlines()
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if not self._is_jitted(node):
                continue
            mesh_names = self._meshes_built(node)
            if not mesh_names:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                d = _dotted(sub.func) or ""
                if d.rsplit(".", 1)[-1] in _COLLECTIVES:
                    findings.append(self.finding(
                        RT005.id, path, sub,
                        f"`{d}` runs under jit while `{node.name}` builds a "
                        f"Mesh ({', '.join(sorted(mesh_names))}) in-trace — "
                        "hoist mesh construction to build time and close "
                        "over it", lines))
        return findings

    @staticmethod
    def _is_jitted(fn: ast.AST) -> bool:
        for dec in fn.decorator_list:
            target = dec
            if isinstance(dec, ast.Call):
                d = _dotted(dec.func) or ""
                if d.rsplit(".", 1)[-1] == "partial" and dec.args:
                    target = dec.args[0]  # functools.partial(jax.jit, ...)
                else:
                    target = dec.func
            d = _dotted(target) or ""
            if d.rsplit(".", 1)[-1] in ("jit", "pjit"):
                return True
        return False

    @staticmethod
    def _meshes_built(fn: ast.AST) -> List[str]:
        out: List[str] = []
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Call):
                d = _dotted(sub.func) or ""
                if d.rsplit(".", 1)[-1] in _MESH_CTORS:
                    out.append(d)
        return out
