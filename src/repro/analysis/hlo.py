"""Post-SPMD HLO text analysis: collective operand bytes.

``compiled.as_text()`` is the per-device optimized module; collectives only
exist after SPMD partitioning, so this is the right artifact. We sum the
*operand* sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, per the roofline spec. Shapes in the text
are per-partition, so the sums are per-device bytes — which is what the
collective roofline term divides by per-chip link bandwidth.

Caveat recorded in DESIGN.md §7: ops inside a while loop appear once in the
text; analysis/roofline.py corrects by per-layer extrapolation.
"""

from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g. "bf16[16,4096,320]{2,1,0}" — capture dtype and dims
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
# result shapes appear between '=' and the opcode: "%x = bf16[...]{...} all-gather("
_LINE_RE = re.compile(
    r"=\s+(?P<shapes>(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<suffix>-start|-done)?\("
)
# iota replica groups: replica_groups=[G,S]<=[N] => groups of size S
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
# explicit: replica_groups={{0,1,2,3},{...}} => count ids in first group
_EXPL_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _EXPL_GROUPS_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


def collective_bytes(hlo_text: str) -> dict:
    """Per-device *operand* bytes by collective type + op counts.

    HLO text lists operands as %refs without shapes, so operand bytes are
    derived from the result shape and the op semantics:
      all-reduce / all-to-all / collective-permute: operand == result
      all-gather:      operand = result / group_size
      reduce-scatter:  operand = result * group_size
    `*-done` ops are skipped (payload counted at `*-start`).
    """
    bytes_by = defaultdict(int)
    count_by = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        if m.group("suffix") == "-done":
            continue  # async pair: counted at -start
        op = m.group("op")
        result = 0
        for dm in _SHAPE_RE.finditer(m.group("shapes")):
            result += _shape_bytes(dm.group(1), dm.group(2))
        g = _group_size(line)
        if op == "all-gather":
            operand = result // g
        elif op == "reduce-scatter":
            operand = result * g
        else:
            operand = result
        bytes_by[op] += operand
        count_by[op] += 1
    return {
        "bytes": dict(bytes_by),
        "count": dict(count_by),
        "total_bytes": int(sum(bytes_by.values())),
    }


# ---------------------------------------------------------------------------
# detlint Level 2: jaxpr-level determinism assertions.
#
# Level 1 (repro.analysis.lint) is purely syntactic; these helpers close the
# gap for properties only visible after tracing — weak-type promotion under
# JAX_ENABLE_X64, collective op counts, and silent recompilation. They are
# used by the x64 guard test (tests/test_detlint.py) and available to any
# test that wants to pin a compiled artifact's shape.
# ---------------------------------------------------------------------------

_WIDE_DTYPES = ("float64", "complex128")


def _iter_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (scan/cond/while bodies, pjit calls, custom_jvp, pallas grids, ...)."""
    import jax.extend.core as jex_core

    seen = set()
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        yield j
        for eqn in j.eqns:
            for v in eqn.params.values():
                for cand in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(cand, jex_core.ClosedJaxpr):
                        stack.append(cand.jaxpr)
                    elif isinstance(cand, jex_core.Jaxpr):
                        stack.append(cand)


def _jaxpr_of(fn, *args, **kwargs):
    import jax

    return jax.make_jaxpr(fn)(*args, **kwargs).jaxpr


def find_f64(fn, *args, **kwargs) -> list:
    """Trace ``fn`` and return every (eqn primitive, var, dtype) whose
    output is f64/c128 — the signature of a weak-type promotion leak.
    Empty list == the computation is f64-clean under the *current* x64
    setting (run it under JAX_ENABLE_X64=1 for the guard to bite)."""
    leaks = []
    for j in _iter_jaxprs(_jaxpr_of(fn, *args, **kwargs)):
        for eqn in j.eqns:
            for out in eqn.outvars:
                dt = getattr(getattr(out, "aval", None), "dtype", None)
                if dt is not None and str(dt) in _WIDE_DTYPES:
                    leaks.append((eqn.primitive.name, str(out), str(dt)))
    return leaks


def assert_no_f64(fn, *args, **kwargs) -> None:
    """Assert no f64/c128 intermediate anywhere in ``fn``'s jaxpr
    (including scan/cond/pjit sub-jaxprs). The historical bug class: a
    bare Python float or np.float64 scalar weakly promoting f32 state
    under JAX_ENABLE_X64=1, silently forking trajectories from the
    x64-off run (PR 5/6 model-stack incident)."""
    leaks = find_f64(fn, *args, **kwargs)
    if leaks:
        head = ", ".join(f"{p}->{v}:{d}" for p, v, d in leaks[:8])
        more = f" (+{len(leaks) - 8} more)" if len(leaks) > 8 else ""
        raise AssertionError(
            f"f64 leak: {len(leaks)} wide-dtype intermediate(s): {head}{more}"
        )


_COLLECTIVE_PRIMS = (
    "psum", "psum2", "all_gather", "all_to_all", "ppermute", "pmax",
    "pmin", "reduce_scatter",
)


def collective_count(fn, *args, **kwargs) -> dict:
    """Count collective primitives in ``fn``'s jaxpr, by primitive name.

    The determinism use: a fixed scenario must emit a *fixed* collective
    schedule — a data-dependent collective count means the reduction
    topology (and hence float summation order) varies run to run. Pin the
    expected dict in a test next to the mesh shape it was derived on.
    """
    counts: dict = {}
    for j in _iter_jaxprs(_jaxpr_of(fn, *args, **kwargs)):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in _COLLECTIVE_PRIMS:
                counts[name] = counts.get(name, 0) + 1
    return counts


class recompile_sentinel:
    """Context manager asserting a jitted fn does not recompile inside the
    ``with`` block::

        step = jax.jit(day_step_fn)
        step(state)                       # warm up
        with recompile_sentinel(step):
            for _ in range(n):            # steady-state loop
                state = step(state)

    A growing cache means some argument is changing shape/dtype/static
    value per call — each recompile is a fresh XLA autotune roll and a
    silent fork of the bitwise contract (and a TEPS cliff)."""

    def __init__(self, jitted_fn, allow: int = 0):
        self._fn = jitted_fn
        self._allow = int(allow)
        self._before = 0

    def _size(self) -> int:
        return int(self._fn._cache_size())

    def __enter__(self):
        self._before = self._size()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        grew = self._size() - self._before
        if grew > self._allow:
            raise AssertionError(
                f"recompile sentinel: jit cache grew by {grew} "
                f"(allowed {self._allow}) — an argument is changing "
                f"shape/dtype/static value between calls"
            )
        return False


def measure_compiled(lowered, compiled) -> dict:
    """One-stop per-device measurement from a compiled cell."""
    ca = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
        }
    except Exception:  # pragma: no cover - backend-dependent
        mem_d = {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
        "memory": mem_d,
        "collectives": coll,
    }
