"""``benchmarks/scope_reduce.py``: the classification of ``op_name`` strings,
its coverage of the step program as the CPU compiles it, self time on a
hand-made trace, and the whole reduction on a trace recorded on the chip
with the HLO text of the program that ran."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_tiny import tiny_cell
from benchmarks import harness, scope_reduce

HERE = Path(__file__).parent
STEP = "jit(_train_step)/"
FWD = STEP + "jvp(MAEPretrainModel)/"
BWD = STEP + "transpose(jvp(MAEPretrainModel))/"
ENC_BWD = BWD + "encoder/jvp(MAEPretrainModel)/encoder/checkpoint/"
ENC_REMAT = ENC_BWD + "rematted_computation/"

# op_name strings as the tiny step's HLO carries them (block numbers vary)
NAMES = [
    (FWD + "encoder/block_1/attn/attn_core/bqhd,bkhd->bhqk/dot_general", "fwd", "enc_attn_core"),
    (FWD + "encoder/block_0/attn/out/dot_general", "fwd", "enc_attn_proj"),
    (FWD + "encoder/block_0/attn/mul", "fwd", "enc_attn_proj"),
    (FWD + "encoder/block_0/mlp/fc1/dot_general", "fwd", "enc_mlp"),
    (FWD + "encoder/block_1/jumbo_mlp/fc2/dot_general", "fwd", "jumbo_mlp"),
    (FWD + "encoder/block_0/ln1/rsqrt", "fwd", "enc_other"),
    (FWD + "encoder/embed/proj/conv_general_dilated", "fwd", "enc_other"),
    (FWD + "encoder/jit(_threefry_fold_in)/TrainState.step_rngs/while/body/closed_call/xor",
     "fwd", "enc_other"),
    (FWD + "encoder/mask/jit(argsort)/sort", "fwd", "mask"),
    (FWD + "mask/jit(_take)/gather", "fwd", "mask"),
    (FWD + "preprocess/div", "fwd", "preprocess"),
    (FWD + "decoder/block_0/attn/attn_core/reduce_max", "fwd", "dec_attn_core"),
    (FWD + "decoder/block_1/attn/q/dot_general", "fwd", "dec_attn_proj"),
    (FWD + "decoder/block_1/mlp/tanh", "fwd", "dec_mlp"),
    (FWD + "decoder/ln/mul", "fwd", "dec_other"),
    (FWD + "decoder_proj/dot_general", "fwd", "dec_other"),
    (FWD + "pixel_proj/dot_general", "fwd", "dec_other"),
    (FWD + "patchify/transpose", "fwd", "loss"),
    (FWD + "loss/jit(_var)/reduce_sum", "fwd", "loss"),
    (ENC_REMAT + "block_1/attn/attn_core/exp", "recompute", "enc_attn_core"),
    (ENC_REMAT + "block_0/attn/k/dot_general", "recompute", "enc_attn_proj"),
    (ENC_REMAT + "block_0/mlp/fc1/dot_general", "recompute", "enc_mlp"),
    (ENC_REMAT + "block_0/jumbo_mlp/integer_pow", "recompute", "jumbo_mlp"),
    (ENC_REMAT + "block_0/ln3/rsqrt", "recompute", "enc_other"),
    (ENC_BWD + "block_1/attn/attn_core/bhqk,bkhd->bhqd/dot_general", "bwd", "enc_attn_core"),
    (ENC_BWD + "block_0/attn/v/transpose", "bwd", "enc_attn_proj"),
    (ENC_BWD + "block_0/mlp/fc2/dot_general", "bwd", "enc_mlp"),
    (ENC_BWD + "block_1/jumbo_mlp/fc1/reduce_sum", "bwd", "jumbo_mlp"),
    (BWD + "encoder/jvp(MAEPretrainModel)/encoder/remat2", "bwd", "enc_other"),
    (BWD + "decoder/block_0/attn/attn_core/neg", "bwd", "dec_attn_core"),
    (BWD + "decoder/block_1/attn/out/dot_general", "bwd", "dec_attn_proj"),
    (BWD + "decoder/block_1/mlp/fc1/dot_general", "bwd", "dec_mlp"),
    (BWD + "decoder/block_0/ln2/add_any", "bwd", "dec_other"),
    (BWD + "mask/jit(_take)/scatter-add", "bwd", "mask"),
    (BWD + "loss/mul", "bwd", "loss"),
    (STEP + "grad_scale/mul", "update", "grad_scale"),
    (STEP + "grad_norm/reduce_sum", "update", "grad_norm"),
    (STEP + "guard/cond", "update", "guard"),
    (STEP + "guard/cond/branch_0_fun/add", "update", "guard"),
    (STEP + "guard/cond/branch_1_fun/optimizer/sqrt", "update", "optimizer"),
    (STEP + "optimizer/mul", "update", "optimizer"),
    (STEP + "jvp(rng)/jit(_threefry_fold_in)/TrainState.step_rngs/while/body/closed_call/xor",
     "other", "rng"),
    (STEP + "jvp(metrics)/reduce_sum", "other", "metrics"),
    (STEP + "transpose(jvp(metrics))/mul", "other", "metrics"),
    (STEP + "metrics/sub", "other", "metrics"),
    (STEP + "grad_accum/while/body/jvp(MAEPretrainModel)/encoder/block_0/mlp/fc1/dot_general",
     "fwd", "enc_mlp"),
    (STEP + "grad_accum/mul", "other", "grad_accum"),
    ("jit(_eval_step)/MAEPretrainModel/encoder/mask/jit(argsort)/sort", "other", "mask"),
    # what the program names nothing in: the alarm the unscoped share raises
    (STEP + "mul", "unscoped", "unscoped"),
    (FWD + "slice", "unscoped", "unscoped"),
    (BWD + "reduce_sum", "unscoped", "unscoped"),
    ("reduce_sum", "unscoped", "unscoped"),
    ("state.params['encoder']['block_0']['attn']['k']['bias']", "unscoped", "unscoped"),
    ("", "unscoped", "unscoped"),
]


@pytest.mark.parametrize("name,phase,part", NAMES, ids=[n[0][-48:] or "empty" for n in NAMES])
def test_classify(name, phase, part):
    assert scope_reduce.classify(name) == (phase, part)
    assert phase in scope_reduce.PHASES and part in scope_reduce.PARTS


def test_the_table_covers_every_phase_and_part():
    assert {p for _, p, _ in NAMES} == set(scope_reduce.PHASES)
    assert {p for _, _, p in NAMES} == set(scope_reduce.PARTS)


@pytest.fixture(scope="module")
def tiny_step_text():
    """HLO text of the benchmark's own step at the test size, from the
    program's record of what it compiled."""
    import jax

    from jumbo_mae_tpu_tpu.obs.trace import programs

    cell = tiny_cell(harness.load_cell("l16_pretrain_b128"))
    driver = harness.load_module("drivers", "train_steps").build(
        cell, devices=jax.devices()[:1], seed=7)
    driver._one_step()
    text = programs()["train_step"].as_text()
    driver.close()
    return text


def test_every_instruction_of_the_step_classifies(tiny_step_text):
    """Coverage: of the instructions the step's own tracing produced (their
    ``op_name`` starts with the jitted function), all but a sliver carry a
    scope. A refactor that drops a name fails here, not on the chip. The run
    that set the limit read 0 of 9 000."""
    scopes = scope_reduce.instruction_scopes(tiny_step_text)
    assert len(scopes) > 5000
    assert all(ph in scope_reduce.PHASES and pt in scope_reduce.PARTS
               for ph, pt in scopes.values())
    traced = [scope_reduce.classify(n) for n in scope_reduce._OP_NAME.findall(tiny_step_text)
              if n.startswith(STEP)]
    assert len(traced) > 5000
    unscoped = sum(c == scope_reduce.UNSCOPED for c in traced)
    assert unscoped / len(traced) < 0.002, unscoped
    seen = set(traced)
    # the cell's step has every phase and every part but the accumulation scan
    assert {ph for ph, _ in seen} >= set(scope_reduce.PHASES) - {"unscoped"}
    assert {pt for _, pt in seen} >= set(scope_reduce.PARTS) - {"grad_accum", "unscoped"}


def test_an_instruction_without_a_scope_takes_its_bodys_or_its_callers():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/grad_norm/mul"}
  ROOT %c = f32[8]{0} copy(%m)
}

%fused_computation.2 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0)
  ROOT %a = f32[8]{0} add(%p.1, %p.1), metadata={op_name="jit(f)/grad_norm/add"}
}

%skip (s: f32[8]) -> f32[8] {
  %s = f32[8]{0} parameter(0)
  ROOT %copy.7 = f32[8]{0} copy(%s)
}

%update (u: f32[8]) -> f32[8] {
  %u = f32[8]{0} parameter(0)
  %copy.8 = f32[8]{0} copy(%u)
  ROOT %fusion.3 = f32[8]{0} fusion(%copy.8), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/guard/cond/branch_1_fun/optimizer/add"}
}

ENTRY %main (x: f32[8], ok: pred[]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %ok = pred[] parameter(1)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(f)/optimizer/add"}
  %conditional = f32[8]{0} conditional(%ok, %fusion.2, %fusion.2), branch_computations={%skip, %update}, metadata={op_name="jit(f)/guard/cond"}
  ROOT %copy.5 = f32[8]{0} copy(%conditional)
}
"""
    scopes = scope_reduce.instruction_scopes(text)
    assert scopes["fusion.1"] == ("update", "grad_norm")  # from its body
    assert scopes["fusion.2"] == ("update", "optimizer")  # its own wins
    assert scopes["copy.5"] == scopes["x"] == scope_reduce.UNSCOPED
    # the compiler's copies inside both branches are the guard's cost
    assert scopes["copy.7"] == scopes["copy.8"] == scopes["conditional"] == ("update", "guard")
    assert scopes["fusion.3"] == ("update", "optimizer")


def _planes(ops, modules):
    ev = lambda rows: [SimpleNamespace(name=n, start_ns=s, duration_ns=d, stats=[])
                       for n, s, d in rows]
    return [SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=ev(modules)),
        SimpleNamespace(name="XLA Ops", events=ev(ops)),
    ]), SimpleNamespace(name="/host:CPU", lines=[])]


def test_self_time_gives_a_childs_time_to_the_child():
    """A ``conditional`` of 100 ns covers three children (20 + 30 + 40): its
    own share is the 10 ns they leave, the scopes sum to the union of the
    intervals, and an operation outside the program's runs is not counted."""
    ops = [
        ("%fusion.1 = f32[] fusion()", 0, 50),
        ("%conditional.3 = () conditional()", 50, 100),
        ("%fusion.7 = f32[] fusion()", 55, 20),
        ("%fusion.8 = f32[] fusion()", 75, 30),
        ("%fusion.9 = f32[] fusion()", 105, 40),
        ("%copy.2 = f32[] copy()", 150, 10),
        ("%fusion.1 = f32[] fusion()", 1000, 50),  # second run: only this op
        ("%fusion.1 = f32[] fusion()", 5000, 50),  # another program's time
    ]
    modules = [("jit__train_step(123)", 0, 160), ("jit__train_step(123)", 1000, 60),
               ("jit_other(9)", 5000, 60)]
    scopes = {"fusion.1": ("fwd", "enc_mlp"), "conditional.3": ("update", "guard"),
              "fusion.7": ("update", "optimizer"), "fusion.8": ("update", "optimizer"),
              "fusion.9": ("update", "optimizer")}
    got = scope_reduce.by_scope(_planes(ops, modules), scopes, "jit__train_step", 2)
    ns = {k: round(v * 1e9 * 2) for k, v in got.items()}
    assert ns == {("fwd", "enc_mlp"): 100, ("update", "guard"): 10,
                  ("update", "optimizer"): 90, scope_reduce.UNSCOPED: 10}
    assert sum(ns.values()) == 160 + 50  # the union of the operations' intervals
    assert scope_reduce.self_times([(0, 10, "a"), (0, 10, "b")]) == [("a", 0), ("b", 10)]


def test_no_device_plane_reads_nothing():
    assert scope_reduce.by_scope(_planes([], [])[1:], {}, "jit__train_step", 3) == {}
    record = {"trace": {"programs": []}}
    assert scope_reduce.table(record) is None
    assert scope_reduce.phase_ms(record, "fwd") is None
    assert scope_reduce.part_ms(record, "unscoped") is None


def test_span_readers_read_the_programs_registry():
    from jumbo_mae_tpu_tpu.obs.trace import span

    assert scope_reduce.span_stats("no_such_span") is None
    before = scope_reduce.span_stats("program_build", prefix=True) or (0, 0.0)
    with span("program_build:probe"):
        pass
    count, seconds = scope_reduce.span_stats("program_build", prefix=True)
    assert count == before[0] + 1 and seconds >= before[1]


def test_reduction_reproduces_the_recorded_scoped_trace():
    """The test-sized step as one TPU v5e ran it (the device plane's two
    lines, two runs): the trace, the HLO text of the executable that ran and
    the table they reduce to. Pins the join by instruction name, the fusion
    and control-flow rules and self time on a real trace: the ``conditional``
    events there cover their children, and the table still sums to the
    busy union ``trace_reduce`` reads from the same planes."""
    from jax.profiler import ProfileData

    from benchmarks import trace_reduce

    want = json.loads((HERE / "recorded_scoped_trace.expected.json").read_text())
    text = gzip.open(HERE / "recorded_scoped_trace.hlo.txt.gz", "rt").read()
    raw = gzip.open(HERE / "recorded_scoped_trace.xplane.pb.gz", "rb").read()
    planes = list(ProfileData.from_serialized_xspace(raw).planes)
    got = scope_reduce.by_scope(planes, scope_reduce.instruction_scopes(text),
                                want["program"], want["runs"])
    table = {f"{ph}/{pt}": round(s * 1e9) for (ph, pt), s in got.items()}
    assert table == want["ns_per_run"]
    busy = trace_reduce.reduce_planes(planes, ())["busy_s"] * 1e9 / want["runs"]
    assert sum(table.values()) == pytest.approx(busy, rel=1e-9)
    assert round(busy) == want["busy_ns_per_run"]
    assert table["update/guard"] > 20000  # the branches' copies, not the 5 us of the cond itself
