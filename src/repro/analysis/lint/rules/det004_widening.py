"""DET004: integer accumulators crossing collectives without widening.

PR 2's contacts bug: per-visit contact counts were summed to int32 and
``psum``-ed across workers — at paper scale the global sum wraps within
one day, and it wraps *differently per mesh shape*, breaking the bitwise
contract in the worst possible way (silently). The day step now widens
to int64 before the contacts psum; this rule keeps it that way.

Heuristic: for every ``psum(...)`` / ``all_gather(...)`` operand, find
the ``.sum()`` / ``jnp.sum(...)`` feeding it and classify the summed
source:

  * a **bool mask** (comparison / mask algebra / bool-dtype zeros) —
    its sum is bounded by the shard width, int32 is provably safe;
  * anything else — the sum is unbounded; it must pass through
    ``.astype(<non-32-bit dtype expr>)`` before the collective, or
    accumulate in one (``jnp.sum(x, dtype=...)``). A *named* dtype
    (``cdtype``, ``COUNT_DTYPE``) counts as a deliberate widening
    decision; a literal ``jnp.int32`` does not.
"""

from __future__ import annotations

import ast

from repro.analysis.lint.engine import is_boolish, local_assignments

_COLLECTIVE_ATTRS = {"psum", "all_gather", "all_to_all", "psum_scatter"}
_NARROW_INT_DTYPES = {"int32", "uint32", "int16", "uint16", "int8", "uint8"}


def _narrow_int_dtype(node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        return node.attr in _NARROW_INT_DTYPES
    if isinstance(node, ast.Name):
        return node.id in _NARROW_INT_DTYPES
    return False


def _is_sum_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return isinstance(f, ast.Attribute) and f.attr == "sum"


def _sum_source(node: ast.Call) -> ast.AST:
    """The expression being summed: ``x.sum()`` -> x, ``jnp.sum(x)`` -> x."""
    f = node.func
    if node.args:  # jnp.sum(x, ...) form
        obj = f.value if isinstance(f, ast.Attribute) else None
        # Method form x.sum(axis=..) has the source as the receiver even
        # with args; module form jnp.sum(x) has it as args[0]. Receivers
        # named like modules (jnp/np) mean module form.
        if isinstance(obj, ast.Name) and obj.id in ("jnp", "np", "numpy",
                                                    "lax"):
            return node.args[0]
        return obj if obj is not None else node.args[0]
    return f.value if isinstance(f, ast.Attribute) else node


class WideningRule:
    code = "DET004"
    description = ("unwidened integer .sum() flowing into psum/all_gather "
                   "(int32 accumulators wrap cross-worker at scale)")

    def _check_operand(self, ctx, call, operand, env):
        """Yield findings for unwidened unbounded sums inside ``operand``."""
        for node in ast.walk(operand):
            if not _is_sum_call(node):
                continue
            src = _sum_source(node)
            if is_boolish(src, env):
                continue  # bounded by shard width — int32 safe
            # Chase one level of local assignment for the source.
            if isinstance(src, ast.Name):
                vals = env.get(src.id, [])
                if vals and all(is_boolish(v, env) for v in vals):
                    continue
            # Is the sum wrapped in a widening astype before the collective,
            # or does it accumulate in a dtype of its own?
            wrapped = self._astype_target(operand, node)
            if wrapped is None:
                wrapped = next((kw.value for kw in node.keywords
                                if kw.arg == "dtype"), None)
            if wrapped is None:
                yield ctx.finding(
                    self.code, call,
                    "unbounded .sum() crosses a collective with no "
                    "explicit dtype: widen with .astype(...) before "
                    "psum/all_gather (int32 wraps at scale)",
                )
            elif _narrow_int_dtype(wrapped):
                yield ctx.finding(
                    self.code, call,
                    "unbounded .sum() is pinned to a 32-bit-or-narrower "
                    "int before a collective: widen (int64 under x64, or "
                    "a named dtype seam like cdtype) before psum",
                )

    @staticmethod
    def _astype_target(operand, sum_call):
        """If ``sum_call`` is the receiver of an ``.astype(X)`` somewhere in
        ``operand``, return X; else None."""
        for node in ast.walk(operand):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                    and node.func.value is sum_call
                    and node.args):
                return node.args[0]
        return None

    def check(self, ctx):
        # Outermost functions claim their collectives first (ast.walk is
        # breadth-first), with an env spanning their whole subtree — so a
        # psum inside a closure still sees the enclosing scope's masks.
        covered = set()
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            env = local_assignments(fn)
            for node in ast.walk(fn):
                if id(node) in covered or not self._is_collective(ctx, node):
                    continue
                covered.add(id(node))
                yield from self._check_operand(ctx, node, node.args[0], env)
        # module level (rare, but keep the rule total)
        for node in ast.walk(ctx.tree):
            if id(node) not in covered and self._is_collective(ctx, node):
                yield from self._check_operand(ctx, node, node.args[0], {})

    @staticmethod
    def _is_collective(ctx, node) -> bool:
        if not (isinstance(node, ast.Call) and node.args):
            return False
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _COLLECTIVE_ATTRS:
            return True
        resolved = ctx.imports.resolve(f)
        return bool(resolved) and resolved.split(".")[-1] in _COLLECTIVE_ATTRS
