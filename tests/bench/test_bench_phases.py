"""Device time by phase of the day loop (``bench/phases.py``).

The attribution is checked on HLO text and events made up here, whose
scopes and own times can be worked out by hand, on a traced md-mini chunk
that the profiler records on the CPU, and through the tool's own set-up
on a throwaway cell.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import phases, trace  # noqa: E402
from test_bench_harness import throwaway_root  # noqa: E402

MS = 1_000_000  # ns

HLO = """HloModule jit_worker, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %gather.3 = f32[8]{0} gather(f32[8]{0} %param_0), metadata={op_name="jit(worker)/while/body/closed_call/vmap(dispatch)/gather" stack_frame_id=3}
  ROOT %multiply.4 = f32[8]{0} multiply(f32[8]{0} %gather.3, f32[8]{0} %gather.3), metadata={op_name="jit(worker)/while/body/closed_call/vmap(combine)/mul"}
}

%fused_computation.2 (param_0.1: f32[8]) -> (f32[8], f32[8]) {
  %param_0.1 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(f32[8]{0} %param_0.1), metadata={op_name="jit(worker)/while/body/closed_call/vmap(seeding)/neg"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %negate.1, f32[8]{0} %negate.1)
}

%fused_computation.3 (param_0.2: s32[8], param_1.2: f32[8]) -> f32[8] {
  %param_0.2 = s32[8]{0:T(1024)} parameter(0)
  %param_1.2 = f32[8]{0:T(1024)} parameter(1)
  %transpose.1 = f32[8]{0:T(1024)} transpose(%param_1.2), dimensions={0}, metadata={op_name="jit(worker)/while/body/closed_call/vmap(combine)/jit(_where)/select_n"}
  ROOT %scatter.1 = f32[8]{0:T(1024)} scatter(%param_0.2, %param_0.2, %transpose.1), update_window_dims={}, inserted_window_dims={0}, to_apply=%add.9
}

%body.5 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(worker)/while/body/closed_call/vmap(dispatch)/gather"}
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %x.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(worker)/while/body/closed_call/vmap(health_update)/select_n"}
  %sort.7 = s32[8]{0} sort(s32[8]{0} %k.1), dimensions={0}, metadata={op_name="jit(worker)/while/body/closed_call/vmap(interactions)/jit(launch)/vmap(dispatch)/sort"}
  %custom-call.9 = (f32[1,8]{1,0}) custom-call(f32[8]{0} %x.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(worker)/while/body/closed_call/vmap(interactions)/jit(launch)/while/body/pallas_call"}
  %custom-call.10 = f32[1,8]{1,0} custom-call(), custom_call_target="AllocateBuffer", metadata={op_name="jit(worker)/while/body/closed_call/vmap(interactions)/jit(launch)/while/body/pallas_call"}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %x.1)
  %gte.5 = f32[8]{0:T(1024)} get-tuple-element(%p.1), index=1, metadata={op_name="jit(worker)/while/body/closed_call/vmap(combine)/jit(_where)/select_n"}
  %sort.8 = (s32[8]{0:T(1024)}, f32[8]{0:T(1024)}) sort(%k.1, %gte.5), dimensions={0}, to_apply=%compare.1
  %fusion.4 = f32[8]{0:T(1024)} fusion(%sort.8, %sort.8), kind=kCustom, calls=%fused_computation.3
  %dynamic-update-slice.7 = f32[7,8]{1,0} dynamic-update-slice(%buf.1, %fusion.1, %i.1), metadata={op_name="jit(worker)/while/body/dynamic_update_slice"}
  ROOT %add.2 = s32[] add(s32[] %i.1, s32[] %one.1), metadata={op_name="jit(worker)/while/body/add"}
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %while.9 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t.1), condition=%cond.4, body=%body.5, metadata={op_name="jit(worker)/while"}
  ROOT %gte.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %while.9), index=1
}
"""


def test_scope_of():
    assert phases.scope_of("jit(worker)/while/body/closed_call/"
                           "vmap(dispatch)/gather") == "dispatch"
    # nested scopes: the innermost wins
    assert phases.scope_of("jit(w)/vmap(interactions)/jit(f)/"
                           "vmap(combine)/mul") == "combine"
    assert phases.scope_of("transpose(jvp(seeding))/mul") == "seeding"
    assert phases.scope_of("jit(worker)/observables/add") == "observables"
    assert phases.scope_of("jit(worker)/while/body/add") is None
    assert phases.scope_of("jit(dispatcher)/add") is None


def test_operands():
    line = ("  %sort.8 = (s32[8]{0:T(1024)}, f32[8]{0:T(1024)}) "
            "sort(%k.1, %gte.5), dimensions={0}, to_apply=%compare.1")
    assert phases._operands(line) == ["k.1", "gte.5"]
    line = ("  ROOT %scatter.1 = f32[8]{0:T(1024)} scatter(%p.2, %t.1), "
            "to_apply=%add.9")
    assert phases._operands(line) == ["p.2", "t.1"]
    assert phases._operands("  %c.1 = f32[8]{0} copy(f32[8]{0} %x.1)") == [
        "x.1"]


def test_op_scopes_by_hand():
    scopes = phases.op_scopes(HLO)
    assert scopes == {
        # inside fused computations: their own op_name
        "gather.3": "dispatch", "multiply.4": "combine", "negate.1":
        "seeding", "transpose.1": "combine",
        # a fusion takes its root's scope (combine), not its own ...
        "fusion.1": "combine",
        # ... and a root with no op_name (a tuple, a scatter XLA made)
        # takes its last scoped operand's
        "tuple.1": "seeding", "fusion.2": "seeding",
        "scatter.1": "combine", "fusion.4": "combine",
        "sort.7": "dispatch",  # nested scopes: the innermost wins
        "custom-call.9": "interactions", "custom-call.10": "interactions",
        # XLA made the sort: no op_name, so its operand's scope
        "gte.5": "combine", "sort.8": "combine",
    }  # copy.3 (no scoped operand), add.2 and dynamic-update-slice.7 (an
    # op_name outside every scope), while.9 and the rest carry none
    # the kernel launches, not the buffer allocations
    assert phases.mosaic_calls(HLO) == {"custom-call.9"}


def test_reduce_by_phase_by_hand():
    host = [("window", 0, 100 * MS)]
    kernel = ("%custom-call.9 = (f32[1,8]{1,0}) custom-call(f32[8]{0} %x.1),"
              " custom_call_target=\"tpu_custom_call\"")
    ops = [("%while.9 = (s32[], f32[8]) while((s32[], f32[8]) %t.1)", 0,
            90 * MS),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x.1)", 10 * MS,
            30 * MS),
           (kernel, 30 * MS, 40 * MS),
           (kernel, 40 * MS, 45 * MS),  # a second launch
           ('%custom-call.10 = f32[1,8]{1,0} custom-call(), '
            'custom_call_target="AllocateBuffer"', 45 * MS, 46 * MS),
           ("%fusion.2 = (f32[8], f32[8]) fusion(f32[8] %x.1)", 50 * MS,
            60 * MS),
           ("%copy.3 = f32[8]{0} copy(f32[8]{0} %x.1)", 60 * MS, 70 * MS),
           ("%sort.7 = s32[8]{0} sort(s32[8]{0} %k.1)", 70 * MS, 80 * MS),
           ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x.1)", 95 * MS,
            110 * MS)]  # clipped at 100
    devices = {"/device:TPU:0": ops}
    r = phases.reduce(devices, host, scopes=phases.op_scopes(HLO),
                      kernels=phases.mosaic_calls(HLO))
    want = dict.fromkeys(phases.SCOPES, 0.0)
    want.update(combine=0.025, interactions=0.016, seeding=0.010,
                dispatch=0.010,
                # the loop's own time (90 - 66) and the copy
                unscoped=0.024 + 0.010)
    assert r["phase_s"] == pytest.approx(want)
    assert list(r["phase_s"]) == list(phases.SCOPES) + ["unscoped"]
    # the phases share out the window's own time, which is the busy time
    # and the time by kind of the accepted reduction
    bare = trace.reduce(devices, host)
    assert r["own_s"] == pytest.approx(sum(r["phase_s"].values()))
    assert r["own_s"] == pytest.approx(bare["busy_s"])
    assert r["own_s"] == pytest.approx(sum(bare["kind_s"].values()))
    assert r["kernel_launches"] == 2
    assert r["unscoped_ops"] == [
        ["%while.9 (while)", pytest.approx(0.024)],
        ["%copy.3 (copy)", pytest.approx(0.010)]]
    # a program without scopes: every operation is unscoped, no launch
    # is counted
    r = phases.reduce(devices, host, scopes={})
    assert r["phase_s"]["unscoped"] == pytest.approx(0.095)
    assert r["kernel_launches"] == 0


# sample_infections' hash (infection) fused into the health update's
# select (health_update), as XLA fuses them on the chip
FUSED_ACROSS = """HloModule jit_worker

%fused_computation.5 (param_0.5: u32[8], param_1.5: pred[8]) -> s32[8] {
  %param_0.5 = u32[8]{0} parameter(0)
  %param_1.5 = pred[8]{0} parameter(1)
  %xor.1 = u32[8]{0} xor(u32[8]{0} %param_0.5, u32[8]{0} %param_0.5), metadata={op_name="jit(worker)/while/body/closed_call/vmap(infection)/xor"}
  %convert.2 = s32[8]{0} convert(u32[8]{0} %xor.1), metadata={op_name="jit(worker)/while/body/closed_call/vmap(infection)/convert_element_type"}
  ROOT %select.3 = s32[8]{0} select(pred[8]{0} %param_1.5, s32[8]{0} %convert.2, s32[8]{0} %convert.2), metadata={op_name="jit(worker)/while/body/closed_call/vmap(health_update)/select_n"}
}

%body.6 (p.6: (s32[], s32[8])) -> (s32[], s32[8]) {
  %p.6 = (s32[], s32[8]{0}) parameter(0)
  %fusion.5 = s32[8]{0} fusion(u32[8]{0} %h.1, pred[8]{0} %m.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(worker)/while/body/closed_call/vmap(infection)/xor"}
}
"""


def test_fused_producer_counts_under_its_consumer():
    """A fusion's whole time goes to its root's scope: the infection hash
    fused into the health update counts under ``health_update``, and
    ``infection`` gets none of it, whatever the fusion's own ``op_name``
    says."""
    scopes = phases.op_scopes(FUSED_ACROSS)
    assert scopes == {"xor.1": "infection", "convert.2": "infection",
                      "select.3": "health_update",
                      "fusion.5": "health_update"}
    ops = [("%fusion.5 = s32[8]{0} fusion(u32[8]{0} %h.1, pred[8]{0} %m.1)",
            0, 10 * MS)]
    r = phases.reduce({"/device:TPU:0": ops}, [("window", 0, 10 * MS)],
                      scopes=scopes)
    assert r["phase_s"]["health_update"] == pytest.approx(0.010)
    assert r["phase_s"]["infection"] == 0.0
    assert r["phase_s"]["unscoped"] == 0.0


def test_scopes_are_the_programs():
    from repro.engine import day

    assert phases.SCOPES == day.SCOPES


def test_cpu_trace_of_a_chunk_by_phase(tmp_path):
    """A traced 7-day md-mini chunk on the jnp backend: every scope is in
    the compiled program, and the phases share out the window's own time."""
    import jax
    from jax import profiler

    from repro.api import observables as obs_lib
    from repro.configs import ScenarioBatch, get_epidemic
    from repro.core import interventions as iv
    from repro.engine import EngineCore

    pop = get_epidemic("md-mini").build()
    batch = ScenarioBatch.from_product(
        interventions={"none": (), "isolate": [iv.Intervention(
            "isolate", iv.CaseThreshold(on=30), iv.Everyone(),
            iv.Isolate())]},
        tau=2e-5, seeds=[3], seed_per_day=20, seed_days=7)
    core = EngineCore(pop, batch, layout="local", backend="jnp",
                      max_seed_per_day=20)
    obs = obs_lib.make_observables(
        ["daily_new_infections", "attack_rate", "peak_day",
         "ensemble_mean_ci", "teps"])
    ctx = obs_lib.ObsContext(num_people=pop.num_people, num_scenarios=2,
                             sweep_axes=(("interventions", (0, 1)),))
    runner = core.runner_fn(7, obs)
    args = (core.params, core.init_state(), obs_lib.init_carries(obs, ctx),
            core.week, core.route)
    jax.block_until_ready(runner(*args))
    profiler.start_trace(str(tmp_path))
    try:
        with profiler.TraceAnnotation("window"):
            hist = jax.block_until_ready(runner(*args))[2]
    finally:
        profiler.stop_trace()
    hlo = runner.lower(*args).compile().as_text()
    assert set(phases.op_scopes(hlo).values()) == set(phases.SCOPES)
    r = phases.summarize(str(tmp_path), hlo)
    got = r["phase_s"]
    assert list(got) == list(phases.SCOPES) + ["unscoped"]
    assert min(got.values()) >= 0
    assert got["interactions"] > 0 and got["seeding"] > 0
    assert sum(got.values()) == pytest.approx(r["own_s"])
    assert r["kernel_launches"] == 0  # jnp: no Pallas launch
    assert int(hist["live_tiles"].sum()) > 0


def test_profile_cell(tmp_path):
    """The tool's own set-up on a throwaway twin-2k cell: phases in ms per
    scenario-day that share out the own time, and the live-tile counter of
    a week with 20 seeded a day."""
    root = throwaway_root(str(tmp_path))
    r = phases.profile_cell(root, "tiny.light", 2 ** 31 + 777, chunks=2)
    assert r["scenario_days"] == 4 * 7 * 2
    assert 0 < r["busy_s"] <= r["window_s"]
    line = phases.per_scenario_day(r)
    assert [k for k in line if k.endswith("_ms")] == [
        f"{s}_ms" for s in phases.SCOPES + ("unscoped", "busy", "own")]
    assert sum(line[f"{s}_ms"] for s in phases.SCOPES + ("unscoped",)) == (
        pytest.approx(line["own_ms"]))
    assert 0 <= line["unscoped_pct"] < 100
    assert line["kernel_launches"] == 0  # jnp: no Pallas launch
    assert line["live_tiles"] > 0
    assert "live_tiles_per_launch" not in line
    # the trace is removed once reduced
    assert not os.path.exists(os.path.join(root, "bench", ".cache",
                                           "phases"))
