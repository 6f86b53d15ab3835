"""Continuous-deployment contracts (serve/publisher.py + publish_doctor).

What the publish path must guarantee:

- **round-trip**: a published tree resolves back bit-exact for f32
  transport and within int8 parity for quantized transport, batch_stats
  included;
- **delta chain**: unchanged leaves ride the base by digest, the chain
  resolves through multiple links, a full tree is forced on the
  ``full_every`` cadence, and the chain survives a publisher restart;
- **integrity**: a corrupted payload, a torn (truncated) payload, a
  swapped base, and a missing base are each *named* failures — never a
  silently wrong tree — and the ``publish.export`` fault site produces
  exactly those artifacts for the chaos harness;
- **gates**: bad steps, a sentinel rollback, the min-interval floor, and
  the eval-metric floor each skip the publish with a journaled reason;
  an export failure journals ``publish_failed`` and never propagates
  into the engine (continuous deployment cannot kill training);
- **billing**: every publish lands a ``publish``-tenant ``tenant_usage``
  journal row through the costmeter;
- **doctor**: ``tools/publish_doctor.py`` exits 0 on a healthy directory
  and 2 on a broken one, naming the broken link.
"""

import json

import numpy as np
import pytest

from jumbo_mae_tpu_tpu import faults
from jumbo_mae_tpu_tpu.serve.publisher import (
    MANIFEST,
    PAYLOAD,
    CheckpointPublisher,
    PublishIntegrityError,
    is_publish_artifact,
    latest_artifact,
    resolve_chain,
    verify_artifact,
)
from jumbo_mae_tpu_tpu.train.engine import RunEngine


@pytest.fixture
def inject():
    yield faults.install_plan
    faults.clear_plan()


def make_params(scale=1.0):
    rng = np.random.default_rng(0)
    return {
        "encoder": {
            "layer0": {
                "kernel": (rng.normal(size=(16, 8)) * scale).astype(np.float32),
                "bias": np.zeros(8, np.float32),
            }
        },
        "pos": np.full((4, 16), scale, np.float32),
    }


def events_of(log, etype):
    return [f for t, f in log if t == etype]


# ------------------------------------------------------------- round-trip


def test_f32_round_trip_is_bit_exact(tmp_path):
    pub = CheckpointPublisher(tmp_path, quant="none")
    params = make_params()
    stats = {"head": {"mean": np.arange(8, dtype=np.float32)}}
    art = pub.publish(4, params, batch_stats=stats)
    assert is_publish_artifact(art)
    got, got_stats, m = resolve_chain(art)
    np.testing.assert_array_equal(
        got["encoder"]["layer0"]["kernel"], params["encoder"]["layer0"]["kernel"]
    )
    np.testing.assert_array_equal(got_stats["head"]["mean"], stats["head"]["mean"])
    assert m["step"] == 4 and m["quant"] == "none"


def test_int8_round_trip_within_parity(tmp_path):
    pub = CheckpointPublisher(tmp_path, quant="int8")
    params = make_params()
    got, got_stats, m = resolve_chain(pub.publish(1, params))
    assert got_stats is None
    ref = params["encoder"]["layer0"]["kernel"]
    q = got["encoder"]["layer0"]["kernel"]
    cos = float((ref * q).sum() / (np.linalg.norm(ref) * np.linalg.norm(q)))
    assert cos > 0.999
    # non-kernel leaves are untouched by PTQ
    np.testing.assert_array_equal(got["pos"], params["pos"])
    assert m["quant_report"]["n_quantized"] == 1


def test_delta_chain_resolves_through_multiple_links(tmp_path):
    pub = CheckpointPublisher(tmp_path, quant="none", full_every=100)
    params = make_params()
    pub.publish(1, params)
    params["pos"] = params["pos"] * 2
    a2 = pub.publish(2, params)
    params["encoder"]["layer0"]["bias"] = np.ones(8, np.float32)
    a3 = pub.publish(3, params)
    m3 = json.loads((a3 / MANIFEST).read_text())
    assert m3["base"]["name"] == a2.name
    assert m3["delta_fraction"] < 1.0
    got, _, _ = resolve_chain(a3)  # pos from a2, kernel from a1, bias from a3
    np.testing.assert_array_equal(got["pos"], params["pos"])
    np.testing.assert_array_equal(
        got["encoder"]["layer0"]["bias"], np.ones(8, np.float32)
    )


def test_full_every_bounds_the_chain(tmp_path):
    pub = CheckpointPublisher(tmp_path, quant="none", full_every=2)
    params = make_params()
    for step in (1, 2, 3):
        params["pos"] = params["pos"] + 1
        pub.publish(step, params)
    # seq 0 full, seq 1 delta, seq 2 full again (2 % full_every == 0)
    m = json.loads((tmp_path / "publish-000002" / MANIFEST).read_text())
    assert m["base"] is None
    assert all(r["where"] == "payload" for r in m["leaves"].values())


def test_chain_survives_publisher_restart(tmp_path):
    params = make_params()
    CheckpointPublisher(tmp_path, quant="none", full_every=100).publish(1, params)
    pub2 = CheckpointPublisher(tmp_path, quant="none", full_every=100)
    params["pos"] = params["pos"] * 3
    a2 = pub2.publish(2, params)
    assert a2.name == "publish-000001"  # sequence resumed, not restarted
    m2 = json.loads((a2 / MANIFEST).read_text())
    assert m2["base"]["name"] == "publish-000000"
    got, _, _ = resolve_chain(a2)
    np.testing.assert_array_equal(got["pos"], params["pos"])


# -------------------------------------------------------------- integrity


def test_corrupted_payload_is_named(tmp_path):
    art = CheckpointPublisher(tmp_path, quant="none").publish(1, make_params())
    pay = art / PAYLOAD
    raw = bytearray(pay.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    pay.write_bytes(bytes(raw))
    with pytest.raises(PublishIntegrityError, match="sha256 mismatch"):
        verify_artifact(art)


def test_torn_payload_is_named(tmp_path):
    art = CheckpointPublisher(tmp_path, quant="none").publish(1, make_params())
    pay = art / PAYLOAD
    pay.write_bytes(pay.read_bytes()[:-10])
    with pytest.raises(PublishIntegrityError, match="torn payload"):
        verify_artifact(art)


def test_missing_base_breaks_the_chain_by_name(tmp_path):
    import shutil

    pub = CheckpointPublisher(tmp_path, quant="none", full_every=100)
    params = make_params()
    pub.publish(1, params)
    params["pos"] = params["pos"] * 2
    a2 = pub.publish(2, params)
    shutil.rmtree(tmp_path / "publish-000000")
    with pytest.raises(PublishIntegrityError, match="publish-000000.*missing"):
        resolve_chain(a2)


def test_swapped_base_fingerprint_is_caught(tmp_path):
    import shutil

    pub = CheckpointPublisher(tmp_path, quant="none", full_every=100)
    params = make_params()
    pub.publish(1, params)
    params["pos"] = params["pos"] * 2
    a2 = pub.publish(2, params)
    # an attacker (or a re-run) replaces the base with a different tree
    shutil.rmtree(tmp_path / "publish-000000")
    other = make_params(scale=7.0)
    CheckpointPublisher(tmp_path / "other", quant="none").publish(9, other)
    (tmp_path / "other" / "publish-000000").rename(tmp_path / "publish-000000")
    with pytest.raises(PublishIntegrityError, match="fingerprint mismatch"):
        resolve_chain(a2)


def test_fault_corrupt_ships_a_poisoned_artifact_verification_catches(
    tmp_path, inject
):
    inject("publish.export:corrupt(4)")
    art = CheckpointPublisher(tmp_path, quant="none").publish(1, make_params())
    # the atomic commit happened — but the manifest seals the pre-fault
    # digests, so verification refuses the bytes before any restore
    with pytest.raises(PublishIntegrityError):
        verify_artifact(art)


def test_fault_raise_is_a_torn_export_nothing_ships(tmp_path, inject):
    inject("publish.export:raise@n<1")
    pub = CheckpointPublisher(tmp_path, quant="none")
    with pytest.raises(OSError):
        pub.publish(1, make_params())
    assert latest_artifact(tmp_path) is None
    # the site fires per-invocation: the retry (next checkpoint) succeeds
    art = pub.publish(2, make_params())
    verify_artifact(art)


# ------------------------------------------------------------------ gates


def run_engine_with_publisher(tmp_path, *, dispatch=None, emit=None, **kw):
    """A 8-step engine with a minimal checkpoint saver + the publisher."""
    params = {"w": {"kernel": np.ones((4, 4), np.float32)}}

    def _dispatch(state, batch, step):
        return state, {"loss": 1.0}

    eng = RunEngine(
        training_steps=8,
        log_interval=2,
        eval_interval=4,
        next_batch=lambda s: s,
        dispatch=dispatch or _dispatch,
        fetch=lambda ms: ms,
    )
    eng.state = type("S", (), {"params": params, "batch_stats": None})()
    log = []
    pub = CheckpointPublisher(
        tmp_path, quant="none", emit=emit or (lambda t, **f: log.append((t, f))), **kw
    )
    pub.register(eng)
    return eng, pub, log


def test_gate_passes_on_clean_windows(tmp_path):
    eng, pub, log = run_engine_with_publisher(tmp_path)
    eng.run(eng.state)
    assert [f["step"] for f in events_of(log, "publish")] == [4, 8]
    assert events_of(log, "publish_skipped") == []
    # billing: the publish tenant appears in the journal
    usage = events_of(log, "tenant_usage")
    assert usage and all(u["tenant"] == "publish" for u in usage)


def test_gate_skips_bad_step_windows(tmp_path):
    def dispatch(state, batch, step):
        return state, {"loss": float("nan") if step == 3 else 1.0}

    eng, pub, log = run_engine_with_publisher(tmp_path, dispatch=dispatch)

    # the train loop's log-window hook computes bad_steps; emulate it
    def classify(e, win):
        win.bad_steps = [
            s for s, m in win.fetched if not np.isfinite(m["loss"])
        ]

    eng._on_log_window.insert(0, classify)
    eng.run(eng.state)
    skipped = events_of(log, "publish_skipped")
    assert [(f["step"], f["reason"]) for f in skipped] == [(4, "bad_steps")]
    assert [f["step"] for f in events_of(log, "publish")] == [8]


def test_gate_skips_after_rollback(tmp_path):
    eng, pub, log = run_engine_with_publisher(tmp_path)
    rolled = []

    def window(e, win):
        if win.step == 2 and not rolled:
            e.request_rollback()

    def restore(e, step, win):
        rolled.append(step)
        return 0

    eng.on_log_window(window)
    eng.on_rollback(restore)
    eng.run(eng.state)
    skipped = events_of(log, "publish_skipped")
    assert skipped and skipped[0]["reason"] == "rollback"


def test_gate_min_interval(tmp_path):
    eng, pub, log = run_engine_with_publisher(tmp_path, min_interval_steps=8)
    eng.run(eng.state)
    assert [f["step"] for f in events_of(log, "publish")] == [4]
    assert [(f["step"], f["reason"]) for f in events_of(log, "publish_skipped")] == [
        (8, "min_interval")
    ]


def test_gate_metric_floor(tmp_path):
    eng, pub, log = run_engine_with_publisher(
        tmp_path, metric_key="val/loss", metric_floor=0.5, metric_sense="below"
    )
    eng.on_eval(lambda e, s, st: {"val/loss": 0.9 if s == 4 else 0.1})
    eng.run(eng.state)
    assert [(f["step"], f["reason"]) for f in events_of(log, "publish_skipped")] == [
        (4, "metric_floor")
    ]
    assert [f["step"] for f in events_of(log, "publish")] == [8]


def test_gate_metric_missing(tmp_path):
    eng, pub, log = run_engine_with_publisher(tmp_path, metric_key="val/loss")
    eng.run(eng.state)  # no eval hook registered → no metrics at all
    assert all(
        f["reason"] == "metric_missing" for f in events_of(log, "publish_skipped")
    )


def test_export_failure_never_kills_training(tmp_path, inject):
    inject("publish.export:raise")
    eng, pub, log = run_engine_with_publisher(tmp_path)
    eng.run(eng.state)  # must complete despite every export failing
    assert eng.exit_reason == "completed"
    failed = events_of(log, "publish_failed")
    assert [f["step"] for f in failed] == [4, 8]
    assert "OSError" in failed[0]["error"]


def test_preemption_checkpoint_never_publishes(tmp_path):
    eng, pub, log = run_engine_with_publisher(tmp_path)
    eng.on_log_window(
        lambda e, win: e.request_stop() if win.step == 2 else None
    )
    eng.run(eng.state)
    assert events_of(log, "publish") == []
    assert events_of(log, "publish_skipped") == []


# ----------------------------------------------------------------- doctor


def test_publish_doctor_ok_and_broken(tmp_path, capsys):
    import sys

    sys.path.insert(0, "tools")
    try:
        import publish_doctor
    finally:
        sys.path.pop(0)

    pub = CheckpointPublisher(tmp_path, quant="none", full_every=100)
    params = make_params()
    pub.publish(1, params)
    params["pos"] = params["pos"] * 2
    a2 = pub.publish(2, params)
    assert publish_doctor.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "OK: 2 artifact(s) verified" in out

    pay = a2 / PAYLOAD
    raw = bytearray(pay.read_bytes())
    raw[0] ^= 0xFF
    pay.write_bytes(bytes(raw))
    assert publish_doctor.main([str(tmp_path)]) == 2
    out = capsys.readouterr().out
    assert "publish-000001" in out and "BROKEN" in out

    assert publish_doctor.main([str(tmp_path / "empty")]) == 2


def test_cost_doctor_surfaces_publish_tenant(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    try:
        import cost_doctor
    finally:
        sys.path.pop(0)

    # a training journal: tenant_usage ledger rows only, no request rows —
    # exactly what a publishing train run leaves behind
    jdir = tmp_path / "journal"
    jdir.mkdir()
    rec = {
        "ts": 1.0,
        "seq": 0,
        "type": "tenant_usage",
        "tenant": "publish",
        "class": "batch",
        "requests": 2,
        "device_s": 0.25,
        "flops": 0.0,
        "waste_device_s": 0.0,
        "window_device_s": 0.25,
        "share": 1.0,
    }
    (jdir / "journal-00000.jsonl").write_text(json.dumps(rec) + "\n")
    out = tmp_path / "chargeback.md"
    assert cost_doctor.main([str(jdir), "--out", str(out)]) == 0
    report = out.read_text()
    assert "| publish | batch | 2 |" in report
    assert "ledger-only tenant(s)" in report
    assert "top consumer: **publish**" in report


def test_engine_cold_start_from_publish_artifact(tmp_path):
    """``InferenceEngine(ckpt=<publish artifact>)`` must resolve the chain
    and serve the published weights — a pool cold-starts straight from the
    newest publish, bit-identical to hot-swapping the same artifact in."""
    from pathlib import Path

    from jumbo_mae_tpu_tpu.config import load_config
    from jumbo_mae_tpu_tpu.infer import InferenceEngine

    recipe = Path(__file__).resolve().parent.parent / "recipes" / "smoke_cpu.yaml"
    cfg = load_config(
        recipe,
        [
            "model.overrides.dtype=float32",
            "model.dec_layers=1",
            "model.dec_dim=32",
            "model.dec_heads=2",
            "model.dec_dtype=float32",
        ],
    )
    imgs = np.random.RandomState(7).randint(0, 256, (2, 32, 32, 3)).astype(np.uint8)
    a = InferenceEngine(cfg, warm_cache=False)
    ref = np.asarray(a.features(imgs))
    params = a._tasks["features"]["variables"]["params"]

    art = CheckpointPublisher(tmp_path, quant="none", full_every=100).publish(
        1, params
    )
    b = InferenceEngine(cfg, ckpt=str(art), warm_cache=False)
    np.testing.assert_array_equal(np.asarray(b.features(imgs)), ref)
