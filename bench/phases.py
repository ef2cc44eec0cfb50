"""Device time by phase of the day loop: a profiler trace's operations
attributed to the program's named scopes, and a tool that traces a few
chunks of one cell and prints them.

    python3 bench/phases.py --workload md-ens.onset --seed 7 --chunks 4

The program runs each phase of its day step under a ``jax.named_scope``
(``repro.engine.day.SCOPES``), and the names reach the compiled module's
``op_name`` metadata. The TPU's operation events carry the instruction
name and not the ``op_name``, so the scope of an event comes from the
compiled module's HLO text, by instruction name (:func:`op_scopes`).

* phases: each operation's own time (``bench.trace.self_times``) under the
  innermost scope of its instruction; operations under none go to
  ``unscoped``;
* kernel launches: the Mosaic custom calls (``tpu_custom_call``) of the
  window under the ``interactions`` scope, the Pallas launches of the
  interaction kernel (:func:`mosaic_calls`).

The tool sets a cell up as the harness does (``bench/harness.py``), then
traces ``--chunks`` replays of one chunk inside a ``window`` span and
prints one JSON line: milliseconds per scenario-day by phase beside the
device's busy time (``bench.trace.reduce``), the top unscoped operations,
kernel launches and live tiles (the program's ``live_tiles`` counter) per
scenario-day. It checks nothing against the reference; ``bench/run.py``
does that.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The program's named scopes, by name: the benchmark reads them from the
# compiled module and imports nothing of the program.
SCOPES = ("interventions", "person_channels", "dispatch", "interactions",
          "combine", "infection", "seeding", "health_update", "reductions",
          "observables")
UNSCOPED = "unscoped"
KERNEL_SCOPE = "interactions"
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def instruction(name: str) -> str:
    """The HLO instruction name of a device operation: ``fusion.192`` for
    ``%fusion.192 = f32[..] fusion(...)`` (the TPU) or ``fusion.192`` (the
    CPU)."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def scope_of(op_name: str):
    """The innermost of ``SCOPES`` in an ``op_name`` path such as
    ``jit(worker)/while/body/vmap(dispatch)/gather``, or None."""
    found = [t for t in re.split(r"[/()]+", op_name) if t in SCOPES]
    return found[-1] if found else None


def _closing(text: str, start: int) -> int:
    """The index of the parenthesis that closes the one at ``start``."""
    depth = 0
    for j in range(start, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            return j
    return len(text)


def _operands(line: str) -> list:
    """The operand names of one HLO instruction line: the ``%`` names in
    the parentheses after its opcode. The shape before the opcode is one
    word (its layout may hold parentheses) or a tuple in parentheses."""
    rest = line.split(" = ", 1)[1].lstrip()
    i = _closing(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    start = rest.find("(", i)
    return re.findall(r"%([\w.\-]+)", rest[start:_closing(rest, start)])


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: scope}`` for the instructions of a compiled
    module's HLO text that lie under one of ``SCOPES``:

    * a fusion takes the scope of its fused computation's root, where
      that has one, else as any other instruction;
    * an instruction with an ``op_name`` takes the innermost scope in it;
    * an instruction with no ``op_name`` at all, one that XLA made (the
      sort and sorted reduction it turns a scatter-add into, a copy),
      takes the scope of its last operand that has one.

    The text lists callees before callers and operands before their
    users, so one pass resolves every instruction."""
    scopes, roots, comp = {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, scope = m.group(2), None
        called = _CALLS.search(line)
        if called and " fusion(" in line:
            scope = roots.get(called.group(1))
        op = _OP_NAME.search(line)
        if scope is None and op is not None:
            scope = scope_of(op.group(1))
        if scope is None and op is None:
            scope = next((scopes[o] for o in reversed(_operands(line))
                          if o in scopes), None)
        if scope is not None:
            scopes[name] = scope
        if m.group(1):
            roots[comp] = scope
    return scopes


def mosaic_calls(hlo_text: str) -> set:
    """The instruction names of a compiled module's Mosaic kernel calls
    (the buffer allocations that are custom calls too are left out)."""
    out = set()
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is not None and MOSAIC_TARGET in line:
            out.add(m.group(2))
    return out


def read_events(pd) -> tuple[dict, list]:
    """As ``bench.trace.read_events``, but a CPU run's operations are
    grouped by thread: operations nest within one thread's line only, so
    each line counts as a device and own times stay whole."""
    from bench import trace

    devices, host = trace.read_events(pd)
    if trace.HOST_PLANE not in devices:
        return devices, host
    devices = {}
    for plane in pd.planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events
                   if e.duration_ns > 0 and "hlo_op" in trace._stats(e)]
            if ops:
                devices[f"{plane.name}/{i}/{line.name}"] = ops
    return devices, host


def reduce(devices: dict, host: list, scopes: dict,
           kernels: set = frozenset(), top: int = 10) -> dict:
    """Phases, kernel launches and the top unscoped operations inside the
    host span ``window``, averaged over the devices. ``scopes`` maps
    instruction names to scopes (:func:`op_scopes`), ``kernels`` names
    the Mosaic calls (:func:`mosaic_calls`). ``own_s`` is the whole own
    time of the window, which the phases share out."""
    from bench import trace

    spans = [h for h in host if h[0] == trace.WINDOW_SPAN]
    if not spans:
        raise ValueError("the trace has no 'window' host span")
    if not devices:
        raise ValueError("the trace holds no device operation")
    _, w0, w1 = spans[-1]
    phases = dict.fromkeys(SCOPES + (UNSCOPED,), 0)
    launches, unscoped = 0, {}
    for ops in devices.values():
        inside = [(name, max(s, w0), min(e, w1)) for name, s, e in ops
                  if min(e, w1) > max(s, w0)]
        for name, _, _, own in trace.self_times(inside):
            op = instruction(name)
            scope = scopes.get(op, UNSCOPED)
            phases[scope] += own
            launches += op in kernels and scope == KERNEL_SCOPE
            if scope == UNSCOPED:
                k = trace.short(name)
                unscoped[k] = unscoped.get(k, 0) + own
    n = len(devices)
    return {
        "phase_s": {k: v / n / 1e9 for k, v in phases.items()},
        "own_s": sum(phases.values()) / n / 1e9,
        "kernel_launches": launches / n,
        "unscoped_ops": [[k, v / n / 1e9] for k, v in
                         sorted(unscoped.items(), key=lambda kv: -kv[1])[:top]],
    }


def summarize(trace_dir: str, hlo_text: str) -> dict:
    """The phases of the newest trace under ``trace_dir``; ``hlo_text`` is
    the compiled module the window ran."""
    from jax.profiler import ProfileData

    from bench import trace

    pd = ProfileData.from_file(trace.newest_xplane(trace_dir))
    return reduce(*read_events(pd), scopes=op_scopes(hlo_text),
                  kernels=mosaic_calls(hlo_text))


def profile_cell(root: str, name: str, seed: int, chunks: int) -> dict:
    """Set one cell up as the harness does, trace ``chunks`` replays of its
    chunk from the window's start state, and reduce them by phase."""
    import jax
    import numpy as np
    from jax import profiler

    from bench import harness, trace, twin_cache

    cell = harness.load_cell(root, name)
    cfg, traffic = cell.config, cell.traffic
    K, d0 = int(traffic["chunk_days"]), int(traffic["start_day"])
    pop, _ = twin_cache.load_or_build(root, cfg["dataset"],
                                      cfg["block_size"])
    core, runner, carries = harness.make_engine(pop, cfg, traffic, seed)
    args = [core.params, core.init_state(), carries, core.week, core.route]
    args[1], _ = harness.advance_chunks(runner, *args, d0 // K)
    jax.block_until_ready(runner(*args))

    trace_dir = os.path.join(root, "bench", ".cache", "phases")
    shutil.rmtree(trace_dir, ignore_errors=True)
    profiler.start_trace(trace_dir)
    try:
        with profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(chunks):
                hist = jax.device_get(jax.block_until_ready(
                    runner(*args))[2])
    finally:
        profiler.stop_trace()
    hlo = runner.lower(*args).compile().as_text()
    pd = profiler.ProfileData.from_file(trace.newest_xplane(trace_dir))
    busy = trace.reduce(*trace.read_events(pd))
    out = reduce(*read_events(pd), scopes=op_scopes(hlo),
                 kernels=mosaic_calls(hlo))
    shutil.rmtree(trace_dir, ignore_errors=True)

    days = len(cfg["arms"]) * K * chunks
    out.update(busy_s=busy["busy_s"], window_s=busy["window_s"],
               scenario_days=days)
    if "live_tiles" in hist:
        out["live_tiles"] = int(np.asarray(hist["live_tiles"],
                                           np.int64).sum()) * chunks
    return out


def per_scenario_day(r: dict) -> dict:
    """The numbers of :func:`profile_cell` per scenario-day."""
    d = r["scenario_days"]
    line = {f"{k}_ms": v * 1e3 / d for k, v in r["phase_s"].items()}
    line.update(busy_ms=r["busy_s"] * 1e3 / d, own_ms=r["own_s"] * 1e3 / d,
                unscoped_pct=100 * r["phase_s"][UNSCOPED] / r["own_s"],
                kernel_launches=r["kernel_launches"] / d)
    if "live_tiles" in r:
        line["live_tiles"] = r["live_tiles"] / d
        if r["kernel_launches"]:
            line["live_tiles_per_launch"] = (r["live_tiles"]
                                             / r["kernel_launches"])
    line["unscoped_ops"] = r["unscoped_ops"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--chunks", type=int, default=4)
    args = ap.parse_args(argv)

    from repro.launch.cli import use_compile_cache

    use_compile_cache()
    r = profile_cell(ROOT, args.workload, args.seed, args.chunks)
    print(json.dumps({"cell": args.workload, "seed": args.seed,
                      "chunks": args.chunks, **per_scenario_day(r)}),
          flush=True)
    return 0


if __name__ == "__main__":
    # The checkout's root, not this directory: bench/trace.py must not
    # shadow the standard library's module of that name.
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
