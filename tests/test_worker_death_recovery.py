"""The designed answer to a dead data worker, proven end-to-end.

The loader refuses to skip a dead worker (``data/loader.py`` raises
"deterministic stream lost") because skipping would silently fork the batch
sequence — the reference instead skipped samples silently on stream errors
(``/root/reference/src/dataset.py:113-119``). That crash-don't-drift call is
only an availability story if the full chain works:

    SIGKILL a worker mid-run → run aborts with the deterministic-stream
    error → restart with ``run.resume=true`` → final params bit-identical
    to a never-interrupted run.

This test drives that chain through the real CLI in subprocesses (the
worker processes are fresh-interpreter children of the CLI process).
"""

import io
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

# subprocess-heavy end-to-end suites: excluded from the <5-min signal
# run (pytest -m "not slow")
pytestmark = pytest.mark.slow

REPO = Path(__file__).resolve().parent.parent


def _write_shards(root: Path, n_shards: int = 2, per_shard: int = 32) -> int:
    from PIL import Image

    from jumbo_mae_tpu_tpu.data import write_tar_samples

    rng = np.random.default_rng(0)
    root.mkdir(parents=True, exist_ok=True)
    idx = 0
    for s in range(n_shards):
        samples = []
        for _ in range(per_shard):
            img = Image.fromarray(
                rng.integers(0, 256, (48, 48, 3), dtype=np.uint8), "RGB"
            )
            buf = io.BytesIO()
            img.save(buf, format="JPEG", quality=90)
            samples.append(
                {
                    "__key__": f"s{idx:05d}",
                    "jpg": buf.getvalue(),
                    "cls": str(idx % 10).encode(),
                }
            )
            idx += 1
        write_tar_samples(str(root / f"train-{s:04d}.tar"), samples)
    return idx


def _cli_env() -> dict:
    from jumbo_mae_tpu_tpu.utils.procenv import cpu_subprocess_env

    env = cpu_subprocess_env(8)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _cli_cmd(shard_root: Path, out: Path, steps: int, resume: bool) -> list[str]:
    return [
        sys.executable,
        "-m",
        "jumbo_mae_tpu_tpu.cli.train",
        "--config",
        str(REPO / "recipes" / "smoke_cpu.yaml"),
        "--set",
        f"run.output_dir={out}",
        f"run.training_steps={steps}",
        f"optim.training_steps={steps}",
        "run.train_batch_size=8",
        "run.eval_interval=3",
        "run.log_interval=3",
        "run.sanity_eval=false",
        "run.synthetic_data=false",
        f"run.resume={'true' if resume else 'false'}",
        f"data.train_shards={shard_root}/train-{{0000..0001}}.tar",
        "data.valid_shards=",
        "data.dataset_size=64",
        "data.shuffle_buffer=8",
        "data.workers=2",
        "data.image_size=32",
    ]


def _worker_pids(cli_pid: int) -> list[int]:
    """Children of the CLI process running the data-worker module."""
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            cmdline = (Path("/proc") / entry / "cmdline").read_bytes()
            status = (Path("/proc") / entry / "status").read_text()
        except OSError:
            continue
        if b"jumbo_mae_tpu_tpu.data._worker" not in cmdline:
            continue
        for line in status.splitlines():
            if line.startswith("PPid:") and int(line.split()[1]) == cli_pid:
                pids.append(int(entry))
    return sorted(pids)


STEPS = 24  # saves at 3, 6, ... — killed long before 24 so death is certain


@pytest.mark.slow
def test_worker_death_then_resume_is_bit_identical(tmp_path):
    _write_shards(tmp_path / "shards")
    env = _cli_env()

    # --- leg A: never interrupted -------------------------------------
    a = subprocess.run(
        _cli_cmd(tmp_path / "shards", tmp_path / "a", STEPS, resume=False),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert a.returncode == 0, a.stdout[-2000:] + a.stderr[-2000:]

    # --- leg B: SIGKILL one worker after the first checkpoint ---------
    proc = subprocess.Popen(
        _cli_cmd(tmp_path / "shards", tmp_path / "b", STEPS, resume=False),
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    # drain the pipes CONCURRENTLY with the watcher loop: the child's
    # startup chatter (XLA cpu_aot_loader E-lines, one per cached program,
    # ~3.5 KB each) can exceed the 64 KB pipe buffer, and an undrained
    # pipe blocks the child mid-run — the watcher then waits forever for a
    # checkpoint that can't be written
    import threading

    bufs: dict[str, list[str]] = {"out": [], "err": []}

    def _drain(stream, key):
        for line in stream:
            bufs[key].append(line)

    readers = [
        threading.Thread(target=_drain, args=(proc.stdout, "out"), daemon=True),
        threading.Thread(target=_drain, args=(proc.stderr, "err"), daemon=True),
    ]
    for t in readers:
        t.start()
    ckpt_step3 = tmp_path / "b" / "smoke_cpu" / "ckpt" / "last" / "3"
    deadline = time.monotonic() + 300
    killed = None
    try:
        while time.monotonic() < deadline and proc.poll() is None:
            if ckpt_step3.exists():
                workers = _worker_pids(proc.pid)
                if workers:
                    killed = workers[0]
                    os.kill(killed, signal.SIGKILL)
                    break
            time.sleep(0.05)
        assert killed is not None, "never saw checkpoint step 3 + live workers"
        proc.wait(timeout=180)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        for t in readers:
            t.join(timeout=30)
        proc.stdout.close()
        proc.stderr.close()
    out, err = "".join(bufs["out"]), "".join(bufs["err"])
    assert proc.returncode != 0, f"run survived a dead worker: {out[-1500:]}"
    assert "deterministic stream lost" in err, err[-2000:]

    # --- leg B resumed: must land exactly where leg A landed ----------
    b2 = subprocess.run(
        _cli_cmd(tmp_path / "shards", tmp_path / "b", STEPS, resume=True),
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert b2.returncode == 0, b2.stdout[-2000:] + b2.stderr[-2000:]

    from jumbo_mae_tpu_tpu.train.checkpoint import restore_params_any

    import jax

    pa = restore_params_any(tmp_path / "a" / "smoke_cpu" / "ckpt")
    pb = restore_params_any(tmp_path / "b" / "smoke_cpu" / "ckpt")
    leaves_a = jax.tree_util.tree_leaves(pa)
    leaves_b = jax.tree_util.tree_leaves(pb)
    assert len(leaves_a) == len(leaves_b)
    for x, y in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
