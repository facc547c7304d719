"""The per-layer readers on the committed traces recorded on one TPU
v5e: ``idle_share`` and ``mfu`` read what they read before the phase
split and the counters joined the run record; the phase readers give
``phases.split``'s numbers; each reader gives nothing where its run
holds nothing to read."""
from pathlib import Path

import pytest

from chipbench import phases, trace
from chipbench.harness import BENCH_DIR, Bench, load_module

DATA = Path(__file__).parent / "data"
QWEN2_FLOPS = 3_249_383_424.0

#: (idle_share, mfu) on each fixture with 256 tokens in the window, as
#: the readers gave them before this record had ``phases``/``counters``
BEFORE = {
    "trace_v5e_1chip.xplane.pb": (99.85053277126534, 3.009937381262448),
    "trace_v5e_1chip_scoped.xplane.pb": (95.50295845452396,
                                         13.15538205431017),
}

PHASE_READERS = {"forward_ms": "pex.forward",
                 "norms_backward_ms": "pex.norms_backward",
                 "stats_ms": "pex.stats",
                 "weight_backward_ms": "pex.weight_backward",
                 "noise_ms": "pex.noise", "optimizer_ms": "optim.adamw"}


def reader(name: str):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def _run(xplane: Path, hlo=None) -> dict:
    return {"trace": trace.reduce_profile(str(xplane), [0]),
            "phases": phases.split(str(xplane), [0], step_hlo=hlo),
            "tokens": 256, "flops_per_token": QWEN2_FLOPS, "chips": 1,
            "device_kind": "TPU v5 lite", "counters": {}}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_idle_share_and_mfu_read_as_before(name):
    run = _run(DATA / name)
    assert (reader("idle_share")(run), reader("mfu")(run)) == BEFORE[name]


def test_the_phase_readers_give_the_split():
    hlo = (DATA / "trace_v5e_1chip_scoped.hlo.txt").read_text()
    run = _run(DATA / "trace_v5e_1chip_scoped.xplane.pb", hlo)
    split = phases.split(str(DATA / "trace_v5e_1chip_scoped.xplane.pb"),
                         [0], step_hlo=hlo)
    for name, scope in PHASE_READERS.items():
        want = split["phases"].get(scope)
        assert reader(name)(run) == (None if want is None else 1e3 * want)
    # the scoped fixture has pex.forward and, nested, pex.stats
    assert reader("forward_ms")(run) > reader("stats_ms")(run) > 0
    assert reader("trainer_idle_ms")(run) == pytest.approx(
        1e3 * split["idle"]["trainer.fetch_loss"])


def test_readers_give_nothing_where_the_run_holds_nothing():
    run = _run(DATA / "trace_v5e_1chip.xplane.pb")     # no step HLO
    for name in PHASE_READERS:
        assert reader(name)(run) is None
    # no step HLO, so no collectives; no trainer.* spans in this trace
    assert reader("collective_ms")(run) is None
    assert reader("trainer_idle_ms")(run) is None
    assert reader("pex_backwards")(run) is None
    untraced = dict(run, trace=None, phases=None)
    for name in list(PHASE_READERS) + ["collective_ms", "trainer_idle_ms",
                                       "idle_share", "mfu"]:
        assert reader(name)(untraced) is None


#: instructions as the x4 cell's compiled step names them: JAX's psum is
#: ``psum.<n>``, a tuple all-reduce keeps the opcode's name
STEP_HLO = """ENTRY %main.1 (p: bf16[8]) -> bf16[8] {
  %fusion.4 = bf16[19008,3584]{1,0:T(8,128)(2,1)} fusion(%p), kind=kLoop
  %psum.102 = bf16[19008,3584]{1,0:T(8,128)(2,1)} all-reduce(%fusion.4), \
channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_72.73
  %all-reduce.24 = (bf16[3584]{0:T(1024)}, f32[]{:T(128)}) all-reduce(%a, %b)
  %all-gather-start.1 = bf16[8]{0} all-gather-start(%c), dimensions={0}
  %all-reduce-scatter-fusion.2 = bf16[8]{0} fusion(%d), kind=kLoop
}
"""


def test_collective_time_is_the_collectives_self_time_a_step():
    assert phases.collective_instructions(STEP_HLO) == {
        "psum.102", "all-reduce.24", "all-gather-start.1"}
    assert phases.collective_instructions(
        (DATA / "trace_v5e_1chip_scoped.hlo.txt").read_text()) == set()
    modules = [(phases.STEP_MODULE, 0, 20)]
    ops = [("fusion.4 bf16[19008,3584]", 0, 4),
           ("psum.102 bf16[19008,3584]", 4, 10),
           ("all-reduce.24", 10, 11),
           ("all-reduce-scatter-fusion.2 bf16[8]", 11, 12)]
    d = phases.device_split(ops, modules, 0, 20, [],
                            collective=phases.collective_instructions(
                                STEP_HLO))
    assert d["collectives_ns"] == 7
    run = {"phases": {"collectives": 7e-3}}
    assert reader("collective_ms")(run) == pytest.approx(7.0)


def test_the_counter_reader_takes_the_window_s_last_step():
    assert reader("pex_backwards")({"counters": {"pex_backwards": 1}}) == 1


def test_each_reader_names_a_layer_and_the_cells_it_reads():
    spec = Bench().spec
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        assert (BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
