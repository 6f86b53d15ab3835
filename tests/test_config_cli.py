"""Config-system tests + end-to-end CLI train smoke runs."""

import json
from pathlib import Path

import pytest

from jumbo_mae_tpu_tpu.config import (
    IMAGENET_TRAIN_SIZE,
    apply_overrides,
    config_from_dict,
    load_config,
    steps_from_epochs,
)

RECIPES = Path(__file__).resolve().parent.parent / "recipes"


def test_defaults_construct():
    cfg = config_from_dict({})
    assert cfg.run.mode == "pretrain"
    assert cfg.optim.name == "adamw"


def test_epochs_resolution():
    cfg = config_from_dict(
        {
            "run": {"train_batch_size": 4096, "epochs": 1600},
            "optim": {"warmup_epochs": 40},
        }
    )
    assert cfg.run.training_steps == IMAGENET_TRAIN_SIZE * 1600 // 4096
    assert cfg.optim.warmup_steps == IMAGENET_TRAIN_SIZE * 40 // 4096
    # optim.training_steps follows run.training_steps for the cosine decay
    assert cfg.optim.training_steps == cfg.run.training_steps


def test_dataset_size_single_source_of_truth():
    # data.dataset_size drives BOTH the epochs→steps math and the resume
    # cursor; the top-level shorthand feeds data.dataset_size too.
    cfg = config_from_dict(
        {
            "run": {"train_batch_size": 100, "epochs": 2},
            "data": {"dataset_size": 1000},
        }
    )
    assert cfg.run.training_steps == 1000 * 2 // 100
    assert cfg.data.dataset_size == 1000

    cfg2 = config_from_dict(
        {"dataset_size": 500, "run": {"train_batch_size": 100, "epochs": 2}}
    )
    assert cfg2.run.training_steps == 500 * 2 // 100
    assert cfg2.data.dataset_size == 500


def test_dataset_size_rejects_non_positive():
    for bad in (0, -5, 1.5, "lots", True):
        with pytest.raises(ValueError, match="dataset_size"):
            config_from_dict({"data": {"dataset_size": bad}})


def test_overrides_dotted_paths():
    doc = apply_overrides({}, ["optim.learning_rate=1e-3", "run.mode=finetune"])
    cfg = config_from_dict(doc)
    assert cfg.optim.learning_rate == 1e-3
    assert cfg.run.mode == "finetune"


def test_repeated_set_flags_accumulate():
    """`--set a=1 --set b=2` must apply BOTH (argparse nargs='*' without
    action='extend' silently drops all but the last --set group)."""
    from jumbo_mae_tpu_tpu.cli.train import build_parser

    ns = build_parser().parse_args(
        ["--set", "run.training_steps=30", "--set", "run.name=x", "b=2"]
    )
    assert ns.overrides == ["run.training_steps=30", "run.name=x", "b=2"]

    doc = apply_overrides({}, ["run.training_steps=30", "run.name=xyz"])
    cfg = config_from_dict(doc)
    assert cfg.run.training_steps == 30
    assert cfg.run.name == "xyz"


def test_dec_overrides_reach_decoder_config():
    """Recipe-surface parity with the reference's --dec-dropout /
    --dec-droppath / --dec-layerscale flags: every DecoderConfig field is
    reachable via model.dec_overrides dotted keys."""
    from jumbo_mae_tpu_tpu.cli.train import build_model

    doc = apply_overrides(
        {},
        [
            "model.dec_overrides.droppath=0.1",
            "model.dec_overrides.dropout=0.05",
            "model.dec_overrides.layerscale=true",
            "model.preset=vit_t16",
        ],
    )
    cfg = config_from_dict(doc)
    model, _, _ = build_model(cfg)
    assert model.decoder_cfg.droppath == 0.1
    assert model.decoder_cfg.dropout == 0.05
    assert model.decoder_cfg.layerscale is True
    # first-class fields still win unless overridden
    assert model.decoder_cfg.layers == cfg.model.dec_layers

    with pytest.raises(TypeError):
        build_model(
            config_from_dict(
                apply_overrides({}, ["model.dec_overrides.bogus=1"])
            )
        )


def test_unknown_key_rejected():
    with pytest.raises(ValueError, match="unknown"):
        config_from_dict({"run": {"bogus_key": 1}})
    with pytest.raises(ValueError, match="sections"):
        config_from_dict({"not_a_section": {}})


@pytest.mark.parametrize("recipe", sorted(p.name for p in RECIPES.glob("*.yaml")))
def test_all_recipes_parse(recipe):
    """Every shipped recipe parses and builds its model: a key or a policy
    the program no longer has is named by the recipe that still carries it."""
    from jumbo_mae_tpu_tpu.cli.train import build_model
    from jumbo_mae_tpu_tpu.models.config import checkpoint_policy

    cfg = load_config(RECIPES / recipe)
    assert cfg.run.training_steps > 0
    _, model_cfg, flops = build_model(cfg)
    assert flops > 0
    checkpoint_policy(getattr(model_cfg, "remat_policy", "none"))


@pytest.mark.parametrize(
    "overrides,error",
    [
        ({"gather_impl": "onehot"}, "unexpected keyword argument 'gather_impl'"),
        ({"grad_ckpt": True, "remat_policy": "dots_no_batch"}, "unknown remat policy"),
    ],
    ids=["gather_impl", "dots_no_batch"],
)
def test_retired_model_options_are_refused(overrides, error):
    """``take`` is the one masking gather and ``none``/``dots`` the remat
    policies: the retired spellings fail like any unknown key or value."""
    import jax

    from jumbo_mae_tpu_tpu.cli.train import build_model

    cfg = config_from_dict(
        {"run": {"mode": "pretrain"},
         "model": {"preset": "vit_t16", "overrides": {"image_size": 32, **overrides}}}
    )
    with pytest.raises((TypeError, ValueError), match=error):
        model, _, _ = build_model(cfg)
        jax.eval_shape(
            model.init,
            {"params": jax.random.key(0), "noise": jax.random.key(1)},
            jax.ShapeDtypeStruct((1, 32, 32, 3), "uint8"),
        )


def test_recipe_peak_lr_matches_reference_math():
    cfg = load_config(RECIPES / "pretrain_vit_b16_in1k_1600ep.yaml")
    # blr 1.5e-4 · 4096/256 = 2.4e-3 (SURVEY §6)
    assert abs(cfg.optim.peak_lr(cfg.run.train_batch_size) - 2.4e-3) < 1e-9


def test_checkpoint_config_mode_policy():
    pre = config_from_dict({"run": {"mode": "pretrain"}}).checkpoint_config()
    assert pre.best_mode == "min" and pre.metric_key == "val/loss"
    ft = config_from_dict({"run": {"mode": "finetune"}}).checkpoint_config()
    assert ft.best_mode == "max" and ft.metric_key == "val/acc1"


@pytest.mark.slow
def test_smoke_pretrain_end_to_end(tmp_path):
    """The 10-step CPU smoke: full loop incl. eval, ckpt, metrics JSONL."""
    from jumbo_mae_tpu_tpu.cli.train import train

    cfg = load_config(
        RECIPES / "smoke_cpu.yaml",
        [f"run.output_dir={tmp_path}", "run.eval_interval=5"],
    )
    metrics = train(cfg)
    assert "val/loss" in metrics and metrics["val/loss"] > 0
    out = tmp_path / "smoke_cpu"
    lines = (out / "smoke_cpu-metrics.jsonl").read_text().strip().splitlines()
    assert any("perf/images_per_sec" in json.loads(l) for l in lines)
    # a CPU count is not a device rate: no MFU against a made-up peak
    assert not any("perf/mfu" in json.loads(l) for l in lines)
    assert (out / "ckpt" / "last").is_dir()


@pytest.mark.slow
def test_sample_exact_resume_end_to_end(tmp_path):
    """VERDICT #7 acceptance: train 6 steps straight through vs train 3 +
    restore + 3 more on REAL shards — final params identical, which only
    holds if the data stream resumes sample-exactly (the resume point is
    mid-epoch: 32 samples / batch 8 → step 3 is 24 samples into epoch 0, so
    a coarse epoch-granular cursor would replay epoch 0 and diverge)."""
    import io

    import numpy as np
    from PIL import Image

    from jumbo_mae_tpu_tpu.cli.train import train
    from jumbo_mae_tpu_tpu.data import write_tar_samples
    from jumbo_mae_tpu_tpu.train.checkpoint import restore_params_any

    rng = np.random.default_rng(0)
    shard_root = tmp_path / "shards"
    shard_root.mkdir()
    idx = 0
    for s in range(2):
        samples = []
        for _ in range(16):
            img = Image.fromarray(
                rng.integers(0, 256, (48, 48, 3), dtype=np.uint8), "RGB"
            )
            buf = io.BytesIO()
            img.save(buf, format="JPEG", quality=90)
            samples.append(
                {"__key__": f"s{idx:05d}", "jpg": buf.getvalue(),
                 "cls": str(idx % 10).encode()}
            )
            idx += 1
        write_tar_samples(str(shard_root / f"train-{s:04d}.tar"), samples)

    def overrides(out, steps):
        return [
            f"run.output_dir={out}",
            f"run.training_steps={steps}",
            "run.eval_interval=3",
            "run.log_interval=3",
            "run.sanity_eval=false",
            "run.synthetic_data=false",
            f"data.train_shards={shard_root}/train-{{0000..0001}}.tar",
            "data.valid_shards=",
            "data.dataset_size=32",
            "data.shuffle_buffer=8",
            "optim.training_steps=6",
        ]

    train(load_config(RECIPES / "smoke_cpu.yaml", overrides(tmp_path / "a", 6)))

    train(load_config(RECIPES / "smoke_cpu.yaml", overrides(tmp_path / "b", 3)))
    train(
        load_config(
            RECIPES / "smoke_cpu.yaml",
            overrides(tmp_path / "b", 6) + ["run.resume=true"],
        )
    )

    pa = restore_params_any(tmp_path / "a" / "smoke_cpu" / "ckpt")
    pb = restore_params_any(tmp_path / "b" / "smoke_cpu" / "ckpt")
    import jax

    for (ka, a), (kb, b) in zip(
        jax.tree_util.tree_leaves_with_path(pa),
        jax.tree_util.tree_leaves_with_path(pb),
    ):
        assert ka == kb
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=0, rtol=0)


@pytest.mark.slow
def test_sigterm_checkpoints_and_exits_cleanly(tmp_path):
    """Graceful preemption: SIGTERM mid-run → the loop checkpoints at the
    next step boundary and exits 0; the checkpoint resumes normally."""
    import os
    import signal
    import subprocess
    import sys as _sys
    import time
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    cmd = [
        _sys.executable, "-m", "jumbo_mae_tpu_tpu.cli.train",
        "--config", str(RECIPES / "smoke_cpu.yaml"),
        "--set", f"run.output_dir={tmp_path}", "run.training_steps=100000",
        "run.eval_interval=100000", "run.log_interval=5",
        "run.sanity_eval=false",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(repo))
    proc = subprocess.Popen(
        cmd, cwd=str(repo), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        metrics = tmp_path / "smoke_cpu" / "smoke_cpu-metrics.jsonl"
        deadline = time.time() + 300
        while time.time() < deadline and not metrics.exists():
            if proc.poll() is not None:
                raise AssertionError(f"train died early:\n{proc.stdout.read()}")
            time.sleep(1)
        assert metrics.exists(), "training never produced metrics"
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
    finally:
        if proc.poll() is None:  # never orphan a 100000-step child
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, out
    assert "preemption checkpoint" in out
    last = tmp_path / "smoke_cpu" / "ckpt" / "last"
    steps = [int(p.name) for p in last.iterdir() if p.name.isdigit()]
    assert steps and max(steps) < 100000


@pytest.mark.slow
def test_smoke_finetune_resume(tmp_path):
    """Classify mode end-to-end + true resume continues the step counter."""
    from jumbo_mae_tpu_tpu.cli.train import train

    overrides = [
        f"run.output_dir={tmp_path}",
        "run.mode=finetune",
        "run.training_steps=4",
        "run.eval_interval=2",
        "run.log_interval=2",
        "model.mixup=0.8",
        "model.cutmix=1.0",
        "model.label_smoothing=0.1",
        "optim.warmup_steps=2",
        "optim.training_steps=4",
        "optim.layer_decay=0.75",
    ]
    cfg = load_config(RECIPES / "smoke_cpu.yaml", overrides)
    m1 = train(cfg)
    assert "val/acc1" in m1
    # resume: bump steps, expect continuation not restart
    cfg2 = load_config(
        RECIPES / "smoke_cpu.yaml",
        overrides + ["run.training_steps=6", "optim.training_steps=6", "run.resume=true"],
    )
    m2 = train(cfg2)
    assert "val/acc1" in m2


def test_gather_pick_cursor_preserves_native_marker(monkeypatch):
    """The multi-host gather/pick pair must carry the native-IO substrate
    marker; dropping it would make every pod-scale native resume fail (or
    worse, mis-resume on the worker path)."""
    import numpy as np

    from jumbo_mae_tpu_tpu.cli import train as cli_train

    snap = {"workers": [[0, 12]], "batches": 2, "native_threads": 2}

    class FakeMHU:
        @staticmethod
        def process_allgather(x):
            return np.stack([np.asarray(x), np.asarray(x)])

    monkeypatch.setattr(cli_train.jax, "process_count", lambda: 2)
    monkeypatch.setattr(cli_train.jax, "process_index", lambda: 1)
    import jax.experimental.multihost_utils as mhu

    monkeypatch.setattr(mhu, "process_allgather", FakeMHU.process_allgather)

    gathered = cli_train._gather_data_cursor(snap)
    assert gathered["native_threads"] == 2
    picked = cli_train._pick_process_cursor(gathered)
    assert picked["native_threads"] == 2
    assert picked["workers"] == [[0, 12]]


def test_sweep_ft_grid_matches_reference_loops():
    """recipes/sweep_ft.py replaces the reference's loop_*.sh wd x lr grids:
    the dry run must enumerate the full 4x2 grid and every override set
    must load cleanly against the finetune recipe."""
    import subprocess
    import sys

    repo = RECIPES.parent
    proc = subprocess.run(
        [sys.executable, str(RECIPES / "sweep_ft.py"), "--dry-run"],
        cwd=str(repo),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-500:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("sweep:")]
    assert len(lines) == 8  # 4 weight decays x 2 learning rates
    import ast

    grid = set()
    for ln in lines:
        overrides = ast.literal_eval(ln.split("sweep:", 1)[1].strip())
        cfg = load_config(RECIPES / "finetune_vit_b16.yaml", overrides)
        assert cfg.optim.layer_decay == 0.65
        assert cfg.run.name.startswith("ft_sweep_wd")
        grid.add((cfg.optim.weight_decay, cfg.optim.learning_rate))
    # the reference's loop_1.sh/loop_2.sh grid, exactly
    assert grid == {
        (wd, lr)
        for wd in (0.06, 0.07, 0.08, 0.09)
        for lr in (1e-3, 3e-3)
    }


def test_pipe_mesh_undercoverage_raises(tmp_path):
    """mesh.pipe that strands devices must fail loudly, and the untouched
    data default must auto-fill the data axis (advisor round-4 finding)."""
    from jumbo_mae_tpu_tpu.cli.train import train

    # 8 devices, pipe=3: auto-filled data=2 covers 6 of 8 -> raise
    cfg = load_config(
        RECIPES / "smoke_cpu.yaml",
        [f"run.output_dir={tmp_path}", "mesh.pipe=3"],
    )
    with pytest.raises(ValueError, match="covers only"):
        train(cfg)


def test_synthetic_iterators_respect_model_label_count(devices):
    """Synthetic batches must draw labels from the MODEL's class count:
    out-of-range labels one-hot to all-zero rows, silently zeroing the CE
    loss and pinning accuracy at 1.0 (round-5 fix)."""
    import jax

    from jumbo_mae_tpu_tpu.cli.train import (
        make_train_iterator,
        make_valid_iterator,
    )
    from jumbo_mae_tpu_tpu.parallel import MeshConfig, create_mesh

    cfg = load_config(
        RECIPES / "smoke_cpu.yaml",
        [
            "run.mode=finetune",
            "model.overrides={mask_ratio: null, image_size: 32, patch_size: 4, labels: 10}",
        ],
    )
    mesh = create_mesh(MeshConfig(data=1, fsdp=1))
    it, _, _, _ = make_train_iterator(cfg, mesh, 8, num_labels=10)
    batch = next(it)
    labels = jax.device_get(batch["labels"])
    assert labels.max() < 10 and labels.min() >= 0, labels

    vit = make_valid_iterator(cfg, mesh, 8, num_labels=10)()
    vlabels = jax.device_get(next(vit)["labels"])
    assert vlabels.max() < 10 and vlabels.min() >= 0, vlabels
