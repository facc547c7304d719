"""The phase split (``phases.py``): idle gaps by the innermost host
span, device time by program and by phase scope, on intervals made by
hand; the scope of each instruction of the trainer's compiled step at
test sizes; and a small scoped trace recorded on one TPU v5e with
``tools/record_scoped_trace.py`` (four steps of matrix products under
``pex.forward`` and, nested, ``pex.stats``, one product unscoped; in
``run_step`` a 1 ms pause, then ``trainer.fetch_loss`` around the wait
for the result and a 3 ms pause; a 2 ms pause in ``loader``)."""
import re
from pathlib import Path

import pytest

from chipbench import phases, trace
from chipbench.reference import common as C

DATA = Path(__file__).parent / "data"
SCOPED = DATA / "trace_v5e_1chip_scoped.xplane.pb"
SCOPED_HLO = DATA / "trace_v5e_1chip_scoped.hlo.txt"


def test_a_gap_within_a_nested_span_goes_to_it():
    spans = [("run_step", 0, 10), ("trainer.dispatch", 1, 3),
             ("trainer.fetch_loss", 3, 9)]
    # [4, 8) lies in fetch_loss; [0, 1) in run_step's own part;
    # [2, 4) 1 in dispatch, 1 in fetch_loss: the later span wins
    assert phases.charge_gaps([(4, 8), (0, 1), (2, 4)], spans) == {
        "trainer.fetch_loss": 6, "run_step": 1}
    assert phases.own_parts(spans) == [(0, 0, 1), (1, 1, 3), (2, 3, 9),
                                       (0, 9, 10)]


def test_the_holding_span_keeps_a_gap_mostly_in_its_own_part():
    spans = [("run_step", 0, 10), ("trainer.finite_check", 8, 9)]
    # [2, 9): 6 in run_step's own part, 1 in finite_check
    assert phases.charge_gaps([(2, 9), (11, 12)], spans) == {
        "run_step": 7, "other": 1}


@pytest.mark.parametrize("gaps", [
    [(0, 5), (9, 20)],
    [(3.5, 4.5), (10, 10.5), (19, 25)],
    [(-3, 1), (4, 4.25)],
])
def test_without_nesting_gaps_are_charged_as_trace_charges_them(gaps):
    spans = [("loader", 0, 4), ("run_step", 4, 10), ("host_fetch", 10, 20)]
    assert phases.charge_gaps(gaps, spans) == trace.charge_gaps(gaps, spans)


def test_operations_belong_to_the_module_that_holds_their_start():
    modules = [("jit_step_fn", 0, 100), ("jit__threefry_split", 120, 125)]
    ops = [("fusion.1", 0, 10), ("while.2", 10, 100), ("add.3", 121, 122),
           ("copy.4", 101, 102)]
    assert phases.module_of(ops, modules) == [
        "jit_step_fn", "jit_step_fn", "jit__threefry_split", "?"]


def test_step_time_goes_to_the_innermost_scope_as_self_time():
    modules = [("jit_step_fn", 0, 100), ("jit_isfinite", 110, 115)]
    ops = [("while.1 (s32[])", 0, 60), ("fusion.2 bf16[8]", 5, 20),
           ("fusion.3 f32[8]", 20, 30), ("convolution.4", 60, 90),
           ("copy.5", 90, 95), ("fusion.9", 95, 100),
           ("and.6", 110, 112)]
    phase_of = {"while.1": "pex.norms_backward", "fusion.2": "pex.stats",
                "fusion.3": "pex.norms_backward",
                "convolution.4": "pex.weight_backward",
                "copy.5": phases.UNSCOPED}
    d = phases.device_split(ops, modules, 0, 120, [("run_step", 0, 120)],
                            phase_of)
    assert d["phases_ns"] == {"pex.norms_backward": 45, "pex.stats": 15,
                              "pex.weight_backward": 30,
                              phases.UNSCOPED: 5, phases.UNMATCHED: 5}
    assert d["modules_ns"] == {"jit_step_fn": 100, "jit_isfinite": 2}
    assert d["busy_ns"] == 102
    assert d["gaps_ns"] == {"run_step": 18}
    # [100, 110) lies between the programs; [112, 115) in jit_isfinite
    assert d["idle_in_modules_ns"] == {"jit_step_fn": 0, "jit_isfinite": 3}
    assert d["longest_gap"] == (10, "run_step", "?")
    assert "phases_ns" not in phases.device_split(ops, modules, 0, 120, [])


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step_fn)/pex.forward/jvp()/while/body/dot_general", "pex.forward"),
    ("jit(step_fn)/pex.norms_backward/transpose(jvp(pex.forward))/while",
     "pex.norms_backward"),
    ("jit(step_fn)/pex.norms_backward/transpose(jvp())/while/body/"
     "closed_call/checkpoint/pex.stats/reduce_sum", "pex.stats"),
    ("jit(step_fn)/pex.norms_backward/transpose(pex.forward)/"
     "jvp(pex.stats)/scatter-add", "pex.stats"),
    ("jit(step_fn)/pex.noise/jit(_normal)/jit(_uniform)/shift_left",
     "pex.noise"),
    ("jit(step_fn)/optim.adamw/sqrt", "optim.adamw"),
    ("jit(step_fn)/jit(_threefry_split)/_inject_rngs/xor", "unscoped"),
    ("jit(step_fn)/reduce_sum", "unscoped"),
])
def test_innermost_scope(op_name, scope):
    assert phases.innermost_scope(op_name) == scope


HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step_fn)/pex.forward/jvp(pex.stats)/square"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %negate.2 = f32[8]{0} negate(%param_0.1)
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %add.3 = f32[8]{0} add(%fusion.2, %x.1), metadata={op_name="jit(step_fn)/optim.adamw/add"}
  ROOT %copy.4 = f32[8]{0} copy(%add.3), metadata={op_name="jit(step_fn)/reduce_sum"}
}
"""


def test_an_instruction_without_metadata_takes_its_called_roots_phase():
    m = phases.step_phases(HLO)
    assert m["fusion.1"] == "pex.stats"        # from its fused root
    assert m["fusion.2"] == phases.UNSCOPED    # whose root has none
    assert m["add.3"] == "optim.adamw"
    assert m["copy.4"] == phases.UNSCOPED


def test_split_leaves_phases_unreported_without_the_step_hlo():
    s = phases.split(str(DATA / "trace_v5e_1chip.xplane.pb"), [0])
    assert s["phases"] is None
    assert s["steps"] == 4
    # a step's modules are its whole busy time
    assert sum(s["modules"].values()) * s["steps"] == \
        pytest.approx(s["busy_s"])


def test_recorded_scoped_trace():
    hlo = SCOPED_HLO.read_text()
    s = phases.split(str(SCOPED), [0], step_hlo=hlo)
    assert s["steps"] == 4
    assert set(s["modules"]) == {phases.STEP_MODULE}
    ph = s["phases"]
    assert set(ph) == {"pex.forward", "pex.stats", phases.UNSCOPED}
    # three products under pex.forward (one nested in pex.stats), one
    # unscoped: each takes about a quarter of the step or more
    step = s["modules"][phases.STEP_MODULE]
    assert sum(ph.values()) == pytest.approx(step)
    assert ph["pex.forward"] > ph["pex.stats"] > 0.2 * step
    assert ph[phases.UNSCOPED] > 0.2 * step
    cov = phases.coverage(s)
    assert cov["accounted_over_busy"] == pytest.approx(1.0)
    assert cov["unmatched_over_step"] == 0
    # each step's wait in trainer.fetch_loss (3 ms pause and all) is
    # charged to it, not to the run_step span that holds it
    assert s["idle"]["trainer.fetch_loss"] > 0.003
    assert cov["trainer_spans_over_run_step_idle"] > 0.99
    assert sum(s["idle"].values()) * s["steps"] == pytest.approx(
        s["window_s"] - s["busy_s"])
    # the text is the recorded step's own: every operation is in it
    executed = {n.split(" ", 1)[0] for n, _, _ in
                phases.read_profile(str(SCOPED), [0])[1][0]}
    assert executed <= set(phases.step_phases(hlo))


@pytest.mark.parametrize("cell", ["qwen2-7b-dpsgd-s1k", "qwen2-7b-plain-s1k"])
def test_every_heavy_instruction_of_the_trainer_step_has_a_phase(cell,
                                                                 tmp_path):
    """The trainer's step at test sizes, compiled on the CPU: each dot,
    convolution, loop and custom call lies in one phase scope, but for
    the key split of ``_inject_rngs``; the DP step has every scope, the
    plain step none of the per-example ones."""
    import jax
    from chipbench import program
    from chipbench.harness import Context
    from chipbench.tests import tiny
    bench = tiny.make(tmp_path / "bench")
    ctx = Context(bench, bench.workload(cell), 7000000019,
                  jax.devices()[:1])
    params = C.nest(ctx.family.init_params(ctx.config, ctx.key(1)))
    tr = program.trainer(ctx.config, params, ctx.args, seed=ctx.seed31,
                         batch=ctx.mix["batch"], seq=ctx.mix["seq"])
    hlo = tr.lower_step(ctx.stream.batch_at(0)).compile().as_text()
    phase_of = phases.step_phases(hlo)
    heavy = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*? "
                       r"(dot|convolution|while|custom-call)\(")
    unscoped = []
    for line in hlo.splitlines():
        m = heavy.match(line)
        if m and phase_of[m.group(1)] == phases.UNSCOPED:
            unscoped.append(line)
    assert all("jit(_threefry_split)" in line for line in unscoped), \
        unscoped
    found = set(phase_of.values()) - {phases.UNSCOPED}
    per_example = {"pex.norms_backward", "pex.stats", "pex.noise"}
    common = {"pex.forward", "pex.weight_backward", "optim.adamw"}
    assert found == (common | per_example if "dpsgd" in cell else common)
