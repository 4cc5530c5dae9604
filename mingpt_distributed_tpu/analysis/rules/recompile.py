"""GL002/GL003/GL004/GL005 — recompile hazards.

The motivating incident (PR 4): silent recompiles cost us enough real
debugging time that we built a *runtime* ``RecompileWatchdog``
(``telemetry/watchdog.py``) that arms after warmup and counts trace
growth. The watchdog catches recompiles in production; these rules catch
the three coding patterns that cause them, at review time:

* **GL002 traced-coercion** — ``str()``/``int()``/``float()``/
  ``bool()`` or an f-string applied to a traced value inside jitted
  code. Under trace these either raise (``int`` on a tracer) or, worse,
  bake a concrete value into the program via a host sync and retrace on
  the next distinct value.
* **GL003 traced-branch** — Python ``if``/``while``/``assert``/ternary
  on a traced value. Same failure shape: ``TracerBoolConversionError``
  at best, a silent per-value specialisation at worst. Branch on static
  args (fine, that's what they're for) or use ``jnp.where``/
  ``jax.lax.cond``.
* **GL004 jit-in-loop** — ``jax.jit(...)`` constructed inside a
  ``for``/``while`` body. A fresh jit wrapper has a fresh trace cache,
  so per-step/per-request construction recompiles every iteration —
  the serving engine's whole design (two lifetime-compiled programs) is
  the counter-pattern.
* **GL005 unhashable-static** — a list/dict/set literal passed at a
  ``static_argnums``/``static_argnames`` position of a module-local
  jitted callable. Static args are cache keys; unhashables raise at
  call time, and mutable-but-hashable wrappers silently key the cache
  on identity.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Set, Tuple

from mingpt_distributed_tpu.analysis.core import (
    FileContext, Finding, Rule, register_rule,
)
from mingpt_distributed_tpu.analysis.jitutil import (
    TracedTaint, call_name, collect_jitted, is_jax_jit, is_partial,
)

_COERCIONS = {"str", "int", "float", "bool", "format"}


def _walk_scope(root: ast.AST):
    """Child nodes of ``root`` without descending into nested function
    definitions (used where a nested def is its own scope)."""
    stack = list(ast.iter_child_nodes(root))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))


@register_rule
class TracedCoercionRule(Rule):
    id = "GL002"
    name = "traced-coercion"
    help = ("str()/int()/float()/bool()/f-string applied to a traced "
            "value inside jitted code — host sync + retrace per value")

    def check_file(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for fn in collect_jitted(ctx.tree):
            taint = TracedTaint(fn)
            for n in ast.walk(fn.node):
                if isinstance(n, ast.Call) \
                        and call_name(n.func) in _COERCIONS and n.args:
                    if taint.expr_traced(n.args[0]):
                        findings.append(self.finding(
                            ctx, n,
                            f"{call_name(n.func)}() on a traced value "
                            f"inside a jitted function — forces a host "
                            f"sync and retraces per concrete value"))
                elif isinstance(n, ast.JoinedStr):
                    for v in n.values:
                        if isinstance(v, ast.FormattedValue) \
                                and taint.expr_traced(v.value):
                            findings.append(self.finding(
                                ctx, n,
                                "f-string formats a traced value inside "
                                "a jitted function — stringifying a "
                                "tracer bakes in (or crashes on) one "
                                "concrete value"))
                            break
        return findings


@register_rule
class TracedBranchRule(Rule):
    id = "GL003"
    name = "traced-branch"
    help = ("Python if/while/assert/ternary on a traced value inside "
            "jitted code — use jnp.where / jax.lax.cond, or mark the "
            "argument static")

    def check_file(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        for fn in collect_jitted(ctx.tree):
            taint = TracedTaint(fn)
            for n in ast.walk(fn.node):
                test = None
                kind = ""
                if isinstance(n, ast.If):
                    test, kind = n.test, "if"
                elif isinstance(n, ast.While):
                    test, kind = n.test, "while"
                elif isinstance(n, ast.Assert):
                    test, kind = n.test, "assert"
                elif isinstance(n, ast.IfExp):
                    test, kind = n.test, "ternary"
                if test is not None and taint.expr_traced(test):
                    findings.append(self.finding(
                        ctx, n,
                        f"Python {kind} on a traced value inside a "
                        f"jitted function — branches must be "
                        f"jnp.where/lax.cond (or the argument made "
                        f"static) or tracing specialises per value"))
        return findings


@register_rule
class JitInLoopRule(Rule):
    id = "GL004"
    name = "jit-in-loop"
    help = ("jax.jit constructed inside a loop body — a fresh wrapper "
            "has a fresh trace cache, so hot loops recompile every "
            "iteration; hoist construction out of the loop")

    def check_file(self, ctx: FileContext) -> List[Finding]:
        findings: List[Finding] = []
        # walk with an explicit loop-depth stack, resetting at function
        # boundaries (a jit built in a def that happens to be defined in
        # a loop runs once per def call, not per loop iteration)
        def visit(node: ast.AST, loop_depth: int) -> None:
            for child in ast.iter_child_nodes(node):
                d = loop_depth
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.Lambda)):
                    d = 0
                elif isinstance(child, (ast.For, ast.AsyncFor, ast.While)):
                    d = loop_depth + 1
                if isinstance(child, ast.Call) and is_jax_jit(child.func) \
                        and loop_depth > 0:
                    findings.append(self.finding(
                        ctx, child,
                        "jax.jit(...) constructed inside a loop body — "
                        "every iteration gets a fresh trace cache and "
                        "recompiles; build the jitted callable once "
                        "outside the loop"))
                visit(child, d)
        visit(ctx.tree, 0)
        return findings


@register_rule
class UnhashableStaticRule(Rule):
    id = "GL005"
    name = "unhashable-static"
    help = ("list/dict/set literal passed at a static_argnums/"
            "static_argnames position — static args are trace-cache "
            "keys and must be hashable (use a tuple)")

    _UNHASHABLE = (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                   ast.SetComp)

    def check_file(self, ctx: FileContext) -> List[Finding]:
        # name -> (static positional indices, static kwarg names)
        statics: Dict[str, Tuple[Set[int], Set[str]]] = {}
        for fn in collect_jitted(ctx.tree):
            if not fn.bound_to:
                continue
            pos = fn.positional_params()
            nums = set(fn.static_nums)
            for name in fn.static_names:
                if name in pos:
                    nums.add(pos.index(name))
            if nums or fn.static_names:
                statics[fn.bound_to] = (nums, set(fn.static_names))
        # assignments of jit calls also bind a name: step = jax.jit(f, ...)
        for n in ast.walk(ctx.tree):
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call) \
                    and is_jax_jit(n.value.func):
                call = n.value
                kw = {k.arg: k.value for k in call.keywords if k.arg}
                nums: Set[int] = set()
                names: Set[str] = set()
                for node in ast.walk(kw.get("static_argnums", ast.Pass())):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, int):
                        nums.add(node.value)
                for node in ast.walk(kw.get("static_argnames", ast.Pass())):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, str):
                        names.add(node.value)
                if not nums and not names:
                    continue
                for t in n.targets:
                    key = call_name(t) if isinstance(t, (ast.Attribute,)) \
                        else (t.id if isinstance(t, ast.Name) else "")
                    if key:
                        statics.setdefault(key, (nums, names))
        if not statics:
            return []
        findings: List[Finding] = []
        for n in ast.walk(ctx.tree):
            if not isinstance(n, ast.Call):
                continue
            key = call_name(n.func)
            if key not in statics:
                continue
            nums, names = statics[key]
            for i, arg in enumerate(n.args):
                if i in nums and isinstance(arg, self._UNHASHABLE):
                    findings.append(self.finding(
                        ctx, arg,
                        f"unhashable literal at static position {i} of "
                        f"{key}() — jit static args are cache keys; "
                        f"pass a tuple"))
            for k in n.keywords:
                if k.arg in names and isinstance(k.value, self._UNHASHABLE):
                    findings.append(self.finding(
                        ctx, k.value,
                        f"unhashable literal for static argument "
                        f"{k.arg!r} of {key}() — jit static args are "
                        f"cache keys; pass a tuple"))
        return findings
