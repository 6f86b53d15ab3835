"""What can be known about the chip path without a chip.

- The Pallas flash-attention kernels compile (AOT, through the TPU compiler
  that is installed here) for a *described* ``v5e:2x2`` at the widths the
  long-context recipes reach. A compile that passes is not a chip run; it
  catches what the interpreter cannot — misaligned slices, too much VMEM.
- ``chip_smoke.py``'s phase functions run end to end on the CPU at
  ``vit_t16`` size (the rehearsal the script's own chip run is preceded by),
  and the script itself refuses to pass without a TPU.
- The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else at
  one fixed path, and nowhere else.
- Nothing on the training path hides a failed compile or a failed step, and
  no parent that fans out children touches a backend itself.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke

REPO = Path(__file__).resolve().parent.parent
SMOKE_RECIPE = str(REPO / "recipes" / "smoke_cpu.yaml")
# the rehearsals check paths, arguments and control flow, so depth is cut to
# one block each side of vit_t16's width: tracing and compiling it is most
# of what a toy trainer run costs
TOY = ["model.overrides.layers=1", "model.dec_layers=1"]


# ------------------------------------------------ AOT for a described v5e


@pytest.fixture(scope="module")
def v5e_chip():
    """One device of a described (not attached) v5e 2x2. The persistent
    compile cache is off for the whole suite (conftest), as it must be
    around these: a described-device entry cannot be read back."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _flash_programs():
    from jumbo_mae_tpu_tpu.ops.pallas.attention import (
        pallas_flash_attention,
        pallas_flash_attention_with_lse,
    )

    def fwd(q, k, v):
        return pallas_flash_attention(q, k, v)

    def loss(q, k, v):
        return pallas_flash_attention(q, k, v).astype(jnp.float32).sum()

    def loss_lse(q, k, v):
        o, lse = pallas_flash_attention_with_lse(q, k, v)
        return o.astype(jnp.float32).sum() + lse.sum()

    return {
        "fwd": fwd,
        "fwd_bwd": jax.grad(loss, argnums=(0, 1, 2)),
        "with_lse_fwd_bwd": jax.grad(loss_lse, argnums=(0, 1, 2)),
    }


# (batch, seq, heads, head_dim): L/16 at 448 and 896 px (encoder head_dim 64,
# decoder 32), H/14 at 448 px (head_dim 80), and 3139 tokens at head_dim 64
@pytest.mark.parametrize("variant", ["fwd", "fwd_bwd", "with_lse_fwd_bwd"])
@pytest.mark.parametrize(
    "shape", [(8, 787, 16, 64), (8, 787, 16, 32), (4, 1027, 16, 80), (2, 3139, 16, 64)]
)
def test_flash_kernels_compile_for_v5e(v5e_chip, shape, variant):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e_chip)
    compiled = jax.jit(_flash_programs()[variant]).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _causal_programs():
    from jumbo_mae_tpu_tpu.ops.pallas.attention import pallas_causal_attention

    def loss(*xs):
        return pallas_causal_attention(*xs).astype(jnp.float32).sum()

    return {"fwd": pallas_causal_attention, "fwd_bwd": jax.grad(loss, argnums=(0, 1, 2, 3, 4))}


# (batch, heads, seq): the language model's published head (qk 128 + 64 with
# the 64 rope columns of k shared by all heads, v 128) at the cell's 8192
# tokens, at a length that is no multiple of the block, and at one whose
# key/value gradients' accumulators the backward kernel holds in two spans
@pytest.mark.parametrize("variant", ["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", [(2, 32, 8192), (1, 8, 2148), (1, 4, 32768)])
def test_causal_kernels_compile_for_v5e(v5e_chip, shape, variant):
    b, h, s = shape
    x = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=v5e_chip)
    args = (x(b, h, s, 128), x(b, h, s, 64), x(b, h, s, 128), x(b, s, 64), x(b, h, s, 128))
    compiled = jax.jit(_causal_programs()[variant]).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == (1 if variant == "fwd" else 2)


def compile_lm_step(recipe: str, chip, monkeypatch, depth_cut: list[str] | None = None):
    """A language recipe's real step through the trainer's own step factory,
    compiled for the described ``chip``: ``(cfg, lm, parameters, compiled)``.
    ``depth_cut`` overrides the recipe's layer counts and nothing else of it
    (tier-1's compiles: one block of every kind the family has)."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
    from jumbo_mae_tpu_tpu.parallel.sharding import batch_sharding, infer_state_sharding
    from jumbo_mae_tpu_tpu.train import make_optimizer, make_train_step
    from jumbo_mae_tpu_tpu.train.state import TrainState, make_base_rng

    # jax.default_backend() is the CPU here; the program picks its kernels by
    # it, so the test answers for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = load_config(recipe, depth_cut)
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=list(chip.device_set))
    model, lm, _ = build_model(cfg)
    tx = make_optimizer(cfg.optim, cfg.run.train_batch_size, num_layers=lm.layers)
    rows, length = cfg.run.train_batch_size, lm.token_row(cfg.data.seq_len)

    def init():
        v = model.init(jax.random.key(0), jnp.zeros((rows, length), jnp.int32))
        state = TrainState.create(apply_fn=model.apply, params=v["params"], tx=tx,
                                  batch_stats=v.get("batch_stats"), rng=make_base_rng(0))
        return state.replace(step=jnp.zeros((), jnp.int32))

    shapes = jax.eval_shape(init)  # shapes only: nothing can be put on the chip
    parameters = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(shapes.params))
    sharding = infer_state_sharding(shapes, mesh)
    described = jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), shapes, sharding)
    tokens = jax.ShapeDtypeStruct((rows, length), jnp.int32,
                                  sharding=batch_sharding(mesh, accum=False))
    step = make_train_step(mesh, sharding, mode="lm", guard_nonfinite=True)
    return cfg, lm, parameters, step.lower(described, {"tokens": tokens}).compile()


def program_bytes(compiled) -> int:
    """What a compiled step holds on the device by the compiler's account."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)


def assert_the_head_walks_its_tokens_in_tiles(text: str, cfg, lm) -> None:
    """A compiled step's text passes ``chip_smoke``'s own check of the head
    (three products a head, all in the forward pass, nothing over the rows
    held larger than a tile's float32 logits or the kernel: PR 41; four
    products with the logits recomputed under a remat) and holds no array of
    all the step's tokens over the rows held, in any dtype."""
    from jumbo_mae_tpu_tpu.ops.head_loss import head_tile

    batch, seq, rows = cfg.run.train_batch_size, cfg.data.seq_len, lm.rows[1]
    assert jax.default_backend() == "tpu"  # compile_lm_step's patch: the sized check runs
    chip_smoke.check_step_runs_the_head_three_times(
        {"train_step": SimpleNamespace(as_text=lambda: text)}, lm, batch * seq, seq)
    tile = head_tile(batch * seq, rows)
    assert tile < batch * seq and (batch * seq) % tile == 0
    for whole in (f"[{batch},{seq},{rows}]", f"[{batch * seq},{rows}]",
                  f"[{rows},{batch * seq}]", f"[{batch * seq // tile},{tile},{rows}]"):
        assert whole not in text, whole
    assert f"f32[{tile},{rows}]" in text  # one tile's logits


def assert_the_step_is_built_a_block_at_a_time(text: str, cfg, lm) -> None:
    """What a compiled step's text holds in every family, counted from ``lm``'s
    own lists so that a depth cut is held to what the whole recipe is: the
    guard adds no ``conditional``; each softmax block (the MTP module's too)
    runs each of the two causal kernels once (a short-convolution block
    none), a window layer's under
    ``swa_core`` and every other under ``attn_core`` (a rematted block keeps
    the forward kernel's output and log-sum-exp; the backward is one kernel,
    PR 37); each linear-attention block runs the forward chunk kernel twice
    and the backward once with no loop left under ``kda_core``, and sends its
    q, k and v through the short-convolution kernels forward, rematted and
    backward (PR 46); each block
    with a rotate-half rope turns its q and its k through the rope kernel
    three times (forward, rematted, transposed); the heads walk their tokens
    in tiles; and every expert layer walks its held pairs in one loop each
    way through the grouped-product kernel."""
    from jumbo_mae_tpu_tpu.models.lm import GQA_KINDS

    assert " conditional(" not in text and "/guard/" in text
    sliding = lm.kinds.count("sliding_attention")
    softmax = lm.layers - lm.kda_layers - lm.kinds.count("conv") + lm.mtp_layers
    assert chip_smoke.causal_kernel_calls(text) == {"fwd": softmax, "bwd": softmax}
    by_scope = {scope: len(re.findall(
        rf'custom-call\([^\n]*/{scope}/causal_attention_\w+/pallas_call"', text))
        for scope in ("attn_core", "swa_core")}
    assert by_scope == {"attn_core": 2 * (softmax - sliding), "swa_core": 2 * sliding}
    assert chip_smoke.bd_kernel_calls(text) == {"fwd": 0, "bwd": 0}  # a causal family: none under bd_core
    assert chip_smoke.kda_kernel_calls(text) == {"fwd": 2 * lm.kda_layers, "bwd": lm.kda_layers,
                                                 "loops": 0}
    assert chip_smoke.short_conv_kernel_calls(text) == dict.fromkeys(
        ("fwd", "recompute", "bwd"), 3 * lm.kda_layers)
    roped = sum(kind in GQA_KINDS and lm.rope(kind) is not None for kind in lm.kinds)
    assert chip_smoke.rope_kernel_calls(text) == roped * 2 * 3
    assert_the_head_walks_its_tokens_in_tiles(text, cfg, lm)
    assert "gmm" in text
    loops = [line for line in text.splitlines()
             if " while(" in line and '/moe/moe_dispatch/while"' in line]
    assert len(loops) == 2 * (lm.layers - lm.first_k_dense + lm.mtp_layers), len(loops)


# tier-1's compile of this family: the dense block and the MTP module, whose
# block is the trunk's expert block (``lm.block(cfg, sparse=True)``) and which
# the recipe has at any depth: the smallest depth with a block of every kind
DEPTH_CUT = ["model.lm.layers=1"]


def assert_the_all_mla_step(text: str, cfg, lm) -> None:
    """The flash and grouped-product kernels are in the step, each block's
    as ``assert_the_step_is_built_a_block_at_a_time`` counts them, and the
    expert layers build nothing a row wide for all 131 072 (token, expert)
    pairs."""
    assert_the_step_is_built_a_block_at_a_time(text, cfg, lm)
    pairs = cfg.run.train_batch_size * cfg.data.seq_len * lm.experts_per_token
    assert pairs == 131_072
    for wide in (f"[{pairs},{lm.dim}]", f"[{pairs},{2 * lm.expert_hidden}]",
                 f"[{pairs},{lm.expert_hidden}]",
                 f"[{pairs // lm.experts_per_token},{lm.experts_per_token},{lm.dim}]"):
        assert wide not in text, wide
    assert re.search(r'op_name="[^"]*/moe_dispatch/while/body/experts/[^"]*pallas_call"', text)


def test_language_model_step_compiles_for_v5e_at_cut_depth(v5e_chip, monkeypatch):
    """The shipped recipe at one of its five layers (the dense block, and the
    MTP block for the expert blocks; widths, sequence, experts and kernels as
    published), for a described v5e: every structural assertion of the full
    compile, which is ``slow``."""
    cfg, lm, _, compiled = compile_lm_step(chip_smoke.LM_RECIPE, v5e_chip, monkeypatch, DEPTH_CUT)
    assert (lm.layers, lm.first_k_dense, lm.mtp_layers) == (1, 1, 1)
    assert_the_all_mla_step(compiled.as_text(), cfg, lm)


# slow, as the other four families' full compiles: 166 s of one worker, and
# the chip run of every cell on every PR is the stronger reading of "fits".
# Run by hand after a change to models/lm.py, ops/ or a language recipe:
# pytest -m slow tests/test_chip_compile*.py
@pytest.mark.slow
def test_language_model_step_compiles_for_v5e_and_fits(v5e_chip, monkeypatch):
    """The real cut of the shipped recipe (680 M parameters, 2 x 8192 tokens)
    through the trainer's own step factory, for a described v5e: what
    ``assert_the_all_mla_step`` holds (five layers + the MTP block: six calls
    of each causal kernel, five expert layers' loops), and what the step
    holds fits the chip with room: the six kept pairs, 6 x (134 217 728 +
    2 097 152) B, are live at the program's peak, yet the heap this compile
    packs comes to 11 199 043 072 B (11 282 566 144 with the heads' whole
    float32 logits and their recompute, before PR 41; 11 290 151 936 with two
    backward kernels; 11 567 921 664 with the forward run twice;
    12 617 840 128 with the log-sum-exp kept in the kernel's lane-padded
    layout); the bound is the reading before PR 41, which the tiled head may
    not pass."""
    cfg, lm, parameters, compiled = compile_lm_step(chip_smoke.LM_RECIPE, v5e_chip, monkeypatch)
    assert parameters == 680_437_760
    assert (lm.layers, lm.first_k_dense, lm.mtp_layers) == (5, 1, 1)
    assert_the_all_mla_step(compiled.as_text(), cfg, lm)  # two heads: six products
    held = program_bytes(compiled)
    assert 6.8e9 < held <= 11_282_566_144, held


# -------------------------------------------- chip_smoke, rehearsed on CPU


@pytest.fixture
def cache_dir_restored():
    """``enable_compile_cache()`` sets a process-wide jax option; put the
    suite's value back after a test that calls it."""
    old_dir = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old_dir)


@pytest.fixture
def compile_cache(tmp_path, monkeypatch, cache_dir_restored):
    """Turn the persistent cache on against a tmp dir for one test, through
    the program's own switch, and put the suite's setting back after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from jumbo_mae_tpu_tpu.utils.procenv import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield Path(enable_compile_cache())
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()


@pytest.fixture(scope="module")
def watch():
    return chip_smoke.CompileWatch()


def test_kernels_phase_rehearsal_interpreted():
    """The comparison against the XLA reference, on a toy shape with the
    kernels interpreted — steered from here, not by an option of the script."""
    got = chip_smoke.phase_kernels(shapes=((1, 130, 2, 32),), interpret=True)
    assert got["mosaic_custom_call"] is False  # interpreted: nothing to find
    assert max(got["max_rel_err_vs_xla"].values()) < chip_smoke.KERNEL_REL_TOL


def test_lm_kernels_phase_rehearsal_interpreted():
    got = chip_smoke.phase_lm_kernels(
        causal=((1, 2, 40, 16, 8, 16), (1, 6, 40, 16, 0, 16, 2, 21)),
        grouped=(64, 32, 24, (41, 0, 9, 6)), rope=((1, 2, 32, 128), (1, 2, 32, 64)),
        conv=((1, 2, 48, 128),), blockdiff=((1, 8, 1, 40, 16, 4), (1, 4, 2, 64, 16, 4)),
        interpret=True)
    assert got["mosaic_custom_call"] is False
    assert set(got["max_rel_err_vs_xla"]) == {"causal@40x16+8/16", "causal@40x16+0/16g3w21",
                                              "blockdiff@2x40x16g8b4", "blockdiff@2x64x16g2b4",
                                              "rope@32x128", "rope@32x64", "grouped@64x32x24",
                                              "short_conv_q@48x128", "short_conv_v@48x128"}
    assert max(got["max_rel_err_vs_xla"].values()) < chip_smoke.KERNEL_REL_TOL


@pytest.mark.parametrize("forwards,passes", [(1, True), (2, False), (0, False)])
def test_lm_train_phase_counts_the_causal_kernels_in_the_step(monkeypatch, forwards, passes):
    """On the chip the phase holds the step program to one run of each causal
    kernel a block: a second forward run (the remat policy lost the names),
    no kernel at all, or no step program fails it."""
    call = ('  %k.{i} = bf16[2,4]{{1,0}} custom-call(%a), custom_call_target="tpu_custom_call", '
            'metadata={{op_name="jit(_train_step)/{phase}/block_{i}/attn/attn_core/'
            'causal_attention_{kernel}/pallas_call" stack_frame_id=1}}\n')
    # a short-convolution block among them has no causal core and adds no call
    lm = SimpleNamespace(kinds=("mla", "conv", "mla"), mtp_layers=1)
    text = "".join(
        call.format(i=i, phase=phase, kernel=kernel)
        for i in range(3)
        for phase, kernel in [("jvp(M)", "fwd")] * (forwards > 0)
        + [("rematted_computation", "fwd")] * (forwards - 1)
        + [("transpose(jvp(M))", "bwd")])
    programs = {"train_step": SimpleNamespace(as_text=lambda: text)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    verdict = chip_smoke.check_step_runs_each_causal_kernel_once_a_block
    if passes:
        assert verdict(programs, lm) == {"fwd": 3, "bwd": 3}
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=f"'fwd': {3 * forwards}.* not 3 times"):
            verdict(programs, lm)
    with pytest.raises(chip_smoke.SmokeFailure, match="no step program"):
        verdict({}, lm)


def test_train_then_resume_phases_rehearsal(tmp_path, compile_cache, watch, capsys):
    """Train 3 steps (eval + checkpoint at the end), then the same command
    with run.resume=true for 1 more: the restore works and the second build
    of the step program is a persistent-cache hit — the test of the cache's
    placement and of the step counter's stable type. One device: the
    sharded layouts have their own rehearsal below."""
    steps, more = 3, 1
    one_device = [*TOY, "mesh.fsdp=1"]
    train = chip_smoke._trainer_overrides(16, steps, steps + more) + one_device
    assert chip_smoke.run_phase(
        "train",
        lambda: chip_smoke.phase_train(SMOKE_RECIPE, train, tmp_path, steps=steps),
        tmp_path,
        watch,
    )
    resume = chip_smoke._trainer_overrides(16, steps + more, steps + more) + one_device
    assert chip_smoke.run_phase(
        "resume",
        lambda: chip_smoke.phase_resume(
            SMOKE_RECIPE, resume, tmp_path, start=steps, steps=more, watch=watch
        ),
        tmp_path,
        watch,
    )
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    train_line, resume_line = lines
    assert train_line["phase"] == "train" and train_line["passed"]
    checked = train_line["checked"]
    assert checked["loss_last"] < checked["loss_first"]
    assert checked["unexpected_recompiles"] == 0
    # a CPU count is not a device rate: the trainer reports no MFU here
    assert checked["mfu_trainer_reported"] is None
    metrics = (tmp_path / "smoke_cpu" / "smoke_cpu-metrics.jsonl").read_text()
    assert "perf/images_per_sec" in metrics
    for key in ("perf/mfu", "perf/tflops_per_chip", "_utilization"):
        assert key not in metrics
    assert resume_line["checked"]["resumed_from"] == steps
    assert resume_line["checked"]["train_step_cache_hits"] >= 1
    assert resume_line["cache_hits"] >= 1
    # the entries went where the variable says and nowhere else
    assert list(compile_cache.glob("*train_step*"))
    assert "SmokeFailure" not in (tmp_path / "resume.log").read_text()


def test_serve_phase_rehearsal(tmp_path):
    got = chip_smoke.phase_serve(SMOKE_RECIPE, TOY, tmp_path, requests=6, max_batch=2)
    for leg in ("bf16", "int8"):
        assert got[leg] == {
            "answered": 6,
            "warmup_compiled": got["ladder"],
            "warmup_loaded": 0,
            "hot_path_compiles": 0,
        }
    assert got["int8_cosine_min"] >= 0.999
    assert got["pool"] == {"replicas": 2, "answered": 6, "hot_path_compiles": 0}


def test_four_chip_phase_rehearsal(tmp_path, devices):
    """mesh.fsdp=4 against its one-device control on virtual CPU devices:
    the mesh and the sharding rules, not the collectives' speed."""
    steps = 2
    got = chip_smoke.phase_fsdp(
        SMOKE_RECIPE,
        chip_smoke._trainer_overrides(16, steps, steps) + TOY,
        tmp_path,
        chips=4,
        steps=steps,
    )
    np.testing.assert_allclose(
        got["loss_sharded"], got["loss_one_chip"], rtol=chip_smoke.LOSS_REL_TOL
    )
    assert got["moments_checked"] >= 2
    assert got["collectives"]["all-gather"] > 0
    # XLA:CPU reports no memory_stats(); the comparison runs on the chip
    assert got["bytes_in_use_per_device_sharded"] == "not reported by this backend"


def test_a_failed_phase_prints_its_line_and_fails(tmp_path, watch, capsys):
    def boom():
        print("chatter that belongs in the log")
        raise RuntimeError("forced")

    assert chip_smoke.run_phase("boom", boom, tmp_path, watch) is False
    out = capsys.readouterr()
    (line,) = [json.loads(ln) for ln in out.out.splitlines()]
    assert line["passed"] is False and "forced" in line["error"]
    assert "chatter" in (tmp_path / "boom.log").read_text()
    assert "chatter" in out.err  # the log's tail is copied to stderr


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("failing", [None, "train"])
def test_main_exit_code_and_last_line(
    tmp_path, monkeypatch, capsys, cache_dir_restored, failing
):
    """main()'s control flow with the phases stubbed out: the last line only
    when every phase passed, the device as JAX reports it, a non-zero exit
    and no ``"ok": true`` when a phase raised."""
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])

    def stub(name):
        def run(*a, **k):
            if name == failing:
                raise RuntimeError(f"{name} forced to raise")
            return {}

        return run

    for name in ("kernels", "train", "resume", "serve", "lm_kernels", "lm_train"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}", stub(name))
    rc = chip_smoke.main(["--out", str(tmp_path)])
    out = capsys.readouterr().out
    if failing is None:
        assert rc == 0
        assert json.loads(out.splitlines()[-1]) == {
            "ok": True,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        }
    else:
        assert rc != 0
        assert '"ok": true' not in out
        lines = {json.loads(ln)["phase"]: json.loads(ln) for ln in out.splitlines()}
        assert not lines["train"]["passed"]
        assert "skipped" in lines["resume"]["error"]  # nothing to resume from
        assert lines["serve"]["passed"]  # later phases still report
        assert lines["lm_train"]["passed"]


def test_script_refuses_to_pass_on_cpu():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout and proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


# ------------------------------------------------- compile-cache placement


@pytest.mark.parametrize("env_dir", ["/x/cache", None])
def test_compile_cache_placement(monkeypatch, cache_dir_restored, env_dir):
    """Variable set: that directory and no other. Unset: one fixed path in
    the checkout — no host hash, pid, time or temp dir in it. The serving
    warm-start cache sits under the same directory, never ``~/.cache``."""
    from jumbo_mae_tpu_tpu.utils import procenv

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(REPO / ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = env_dir
    monkeypatch.setenv("JUMBO_WARMCACHE", "1")
    assert procenv.compile_cache_dir() == want
    assert procenv.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert procenv.default_warmcache_dir() == os.path.join(want, "warmcache")
    monkeypatch.setenv("JUMBO_WARMCACHE", "0")
    assert procenv.default_warmcache_dir() is None


def test_one_place_sets_the_cache_path_and_every_entry_point_calls_it():
    sources = {
        p: p.read_text()
        for p in [*REPO.glob("*.py"), *REPO.glob("tools/*.py"),
                  *(REPO / "jumbo_mae_tpu_tpu").rglob("*.py")]
    }
    setters = [
        str(p.relative_to(REPO))
        for p, text in sources.items()
        if re.search(r"""config\.update\(\s*["']jax_compilation_cache_dir""", text)
    ]
    assert setters == ["jumbo_mae_tpu_tpu/utils/procenv.py"]
    for entry in ("jumbo_mae_tpu_tpu/cli/train.py", "jumbo_mae_tpu_tpu/cli/predict.py",
                  "jumbo_mae_tpu_tpu/cli/batch.py", "chip_smoke.py"):
        assert "enable_compile_cache()" in sources[REPO / entry], entry
    # no cache under the home directory
    assert "expanduser" not in sources[REPO / "jumbo_mae_tpu_tpu/utils/procenv.py"]


@pytest.mark.parametrize("package", ["models", "ops", "train", "parallel"])
def test_the_compute_path_reads_no_environment(package):
    """What the step program is comes from the configuration and the shapes:
    no module of the compute path takes a switch from the process
    environment, where no recipe, test or compile-cache key can see it."""
    readers = [
        str(p.relative_to(REPO))
        for p in sorted((REPO / "jumbo_mae_tpu_tpu" / package).rglob("*.py"))
        if re.search(r"\benviron\b|\bgetenv\b", p.read_text())
    ]
    assert readers == []


# ------------------------------------------- no fallback on the train path


@pytest.fixture(scope="module")
def tiny_train_state():
    from jumbo_mae_tpu_tpu.models import DecoderConfig, MAEPretrainModel, preset
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
    from jumbo_mae_tpu_tpu.train import (
        OptimConfig,
        create_sharded_state,
        make_optimizer,
    )

    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=jax.devices()[:1])
    enc = preset("vit_t16", image_size=16, patch_size=8, mask_ratio=0.75,
                 labels=None, dtype="float32", layers=1)
    module = MAEPretrainModel(enc, DecoderConfig(layers=1, dim=16, heads=2, dtype="float32"))
    batch = {"images": np.zeros((2, 16, 16, 3), np.uint8)}
    tx = make_optimizer(OptimConfig(name="adamw", training_steps=4, warmup_steps=1), 2)
    state, sharding = create_sharded_state(module, tx, batch, mesh, mode="pretrain")
    return mesh, state, sharding, batch


@pytest.mark.parametrize("failing", ["compile", "execute"])
def test_train_step_failure_raises_with_no_second_route(
    monkeypatch, tiny_train_state, failing
):
    """A failed ``lower().compile()`` — or a failed execution of the compiled
    step — raises, on every call: no plain-jit route runs the step anyway."""
    from jumbo_mae_tpu_tpu.train import make_train_step

    mesh, state, sharding, batch = tiny_train_state
    step = make_train_step(mesh, sharding, mode="pretrain")
    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError(f"{failing} failed on the device")

    if failing == "compile":
        monkeypatch.setattr(jax.stages.Lowered, "compile", boom)
    else:
        step(state, batch)  # compiles; the state is donated, but never read again
        monkeypatch.setattr(jax.stages.Compiled, "__call__", boom)
    for _ in range(2):
        with pytest.raises(RuntimeError, match=f"{failing} failed on the device"):
            step(state, batch)
    assert calls["n"] == 2  # asked again, not routed around


def test_guarded_step_is_branch_free_and_in_place_on_v5e(v5e_chip, tiny_train_state):
    """The divergence guard's gate, as the chip's compiler sees it: the
    guarded step compiled for a described v5e holds no ``conditional`` (the
    ``lax.cond`` it replaced cost the L/16 step 9.2 ms of 235 in copies and
    waits inside its branches — PERF.md, PR 25), the donated state is
    updated in place (the aliased bytes cover every array of it), and the
    shared jumbo MLP's kernel gradients are one product each (PR 39)."""
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh
    from jumbo_mae_tpu_tpu.parallel.sharding import batch_sharding, infer_state_sharding
    from jumbo_mae_tpu_tpu.train import make_train_step

    _, state, _, batch = tiny_train_state
    mesh = create_mesh(MeshConfig(data=1, fsdp=1), devices=list(v5e_chip.device_set))
    # shapes only: nothing can be put on a device that is not attached
    shapes = jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    sharding = infer_state_sharding(shapes, mesh)
    described = jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d), shapes, sharding
    )
    images = jax.ShapeDtypeStruct(
        batch["images"].shape, batch["images"].dtype,
        sharding=batch_sharding(mesh, accum=False),
    )
    step = make_train_step(mesh, sharding, mode="pretrain", guard_nonfinite=True)
    compiled = step.lower(described, {"images": images}).compile()
    text = compiled.as_text()
    assert "/guard/" in text and " conditional(" not in text
    # the shared jumbo MLP's two kernel gradients are the deferred products,
    # under the part's own scope; no block forms a kernel-shaped partial
    products = [re.search(r'op_name="([^"]*)"', line).group(1) for line in text.splitlines()
                if re.search(r"= f32\[(192,768|768,192)\]\S* (convolution|dot)\(", line)]
    assert len(products) == 2, products
    assert all("transpose(" in p and "/encoder/jumbo_mlp/fc" in p and "/block_" not in p
               for p in products), products
    state_bytes = sum(
        int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        for leaf in jax.tree_util.tree_leaves(shapes)
        if not jax.dtypes.issubdtype(leaf.dtype, jax.dtypes.prng_key)
    )
    assert compiled.memory_analysis().alias_size_in_bytes >= state_bytes


# --------------------------------------------------- one process per chip

_GUARD = """
import subprocess, sys
sys.path.insert(0, {repo!r})

class FirstChild(Exception):
    pass

def refuse(*a, **k):
    raise FirstChild

subprocess.run = subprocess.Popen = refuse
{body}
"""

_GUARD_BODIES = {
    "train_elastic": """
from jumbo_mae_tpu_tpu.cli import train
try:
    train.main(["--elastic", "2", "--config", {recipe!r},
                "--set", "run.output_dir=" + {out!r}])
except FirstChild:
    pass
from jax._src import xla_bridge
assert not xla_bridge.backends_are_initialized(), "the supervisor touched a backend"
""",
}


@pytest.mark.parametrize("parent", sorted(_GUARD_BODIES))
def test_parents_that_start_children_never_touch_a_backend(tmp_path, parent):
    """A parent that has touched JAX holds the chip its children need. Run
    each fan-out parent's ``main`` up to its first child and look."""
    body = _GUARD_BODIES[parent].format(out=str(tmp_path / "o"), recipe=SMOKE_RECIPE)
    proc = subprocess.run(
        [sys.executable, "-c", _GUARD.format(repo=str(REPO), body=body)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
