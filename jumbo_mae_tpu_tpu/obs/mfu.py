"""Analytic FLOP counting and MFU reporting for the Jumbo-MAE workloads.

The reference published no throughput or MFU numbers at all (SURVEY §5/§6);
this module closes that observability gap. FLOPs are counted from the model
configs analytically (matmuls only — elementwise work is bandwidth, not MXU),
so MFU = achieved / peak is comparable across chips and runs. Lives in the
telemetry subsystem since the train loop exports the resulting MFU/throughput
through the metrics registry (``utils/mfu.py`` remains as a compat shim).
"""

from __future__ import annotations

from dataclasses import dataclass

# Peak dense bf16 TFLOP/s per chip by TPU generation (public spec sheet
# numbers). A device that is not here is an error, never a default.
PEAK_TFLOPS = {
    "v2": 46.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5e": 197.0,
    # PJRT device_kind spells the e-variants "lite": 'TPU v5 lite',
    # 'TPU v6 lite' (the v5e key alone never matches a real v5e)
    "v5 lite": 197.0,
    "v5litepod": 197.0,
    "v5p": 459.0,
    "v6e": 918.0,
    "v6 lite": 918.0,
}

# The spelling aliases above all collapse onto one canonical generation —
# every consumer (peak flops here, the HBM/ICI tables in ``obs/perfmodel``)
# resolves device_kind through ONE normalizer so the "v5 lite never matched
# v5e" bug class can't come back per-table.
CANONICAL_KINDS = {"v5 lite": "v5e", "v5litepod": "v5e", "v6 lite": "v6e"}


def normalize_device_kind(kind: str) -> str | None:
    """Map a raw PJRT ``device_kind`` string ('TPU v5 lite', 'TPU v4', ...)
    to its canonical generation key ('v5e', 'v4'), or None if unmatched."""
    k = str(kind).lower()
    for gen in sorted(PEAK_TFLOPS, key=len, reverse=True):
        if gen in k:
            return CANONICAL_KINDS.get(gen, gen)
    return None


def lookup_peak_tflops(kind: str) -> float:
    """Peak bf16 TFLOP/s for a device_kind string. A kind that is not in
    the table is an error, not a default: a utilization against a guessed
    peak is not a measurement."""
    gen = normalize_device_kind(kind)
    if gen is None:
        raise ValueError(
            f"device_kind {kind!r} has no PEAK_TFLOPS entry — add its "
            "spec-sheet peak to obs/mfu.py before reporting utilization on it"
        )
    return PEAK_TFLOPS[gen]


def _attention_flops(seq: int, dim: int, *, causal: bool = False) -> float:
    """Matmul FLOPs for one MHSA block on one sample: qkv+out projections and
    the two (N,N) einsums. 2·m·n·k per matmul."""
    proj = 4 * 2 * seq * dim * dim
    scores = 2 * 2 * seq * seq * dim
    if causal:
        scores /= 2
    return proj + scores


def _mlp_flops(seq: int, dim: int, hidden: int) -> float:
    return 2 * 2 * seq * dim * hidden


def encoder_flops_per_image(cfg, *, masked: bool) -> float:
    """Forward FLOPs for the Jumbo-ViT encoder on one image.

    ``masked=True`` uses the MAE visible-token count (``cfg.keep_len``), the
    whole point of encoder-on-visible-only MAE.
    """
    patches = cfg.keep_len if masked else cfg.num_patches
    seq = patches + cfg.num_cls_tokens
    d = cfg.dim
    per_layer = (
        _attention_flops(seq, d)
        + _mlp_flops(patches, d, cfg.hidden_dim)  # patch-token FF
        + _mlp_flops(1, cfg.num_cls_tokens * d, 4 * cfg.num_cls_tokens * d)  # jumbo MLP
    )
    # patchify conv runs on ALL patches (masking happens after embedding)
    embed = 2 * cfg.num_patches * d * (cfg.patch_size**2 * 3)
    return cfg.layers * per_layer + embed


def decoder_flops_per_image(enc_cfg, dec_cfg) -> float:
    seq = enc_cfg.num_patches + enc_cfg.num_cls_tokens
    d = dec_cfg.dim
    per_layer = _attention_flops(seq, d) + _mlp_flops(seq, d, dec_cfg.hidden_dim)
    proj_in = 2 * seq * enc_cfg.dim * d
    proj_out = 2 * enc_cfg.num_patches * d * (enc_cfg.patch_size**2 * 3)
    return dec_cfg.layers * per_layer + proj_in + proj_out


def pretrain_flops_per_image(enc_cfg, dec_cfg, *, training: bool = True) -> float:
    fwd = encoder_flops_per_image(enc_cfg, masked=True) + decoder_flops_per_image(
        enc_cfg, dec_cfg
    )
    return fwd * (3.0 if training else 1.0)  # bwd ≈ 2× fwd


def classify_flops_per_image(enc_cfg, *, training: bool = True) -> float:
    fwd = encoder_flops_per_image(enc_cfg, masked=False)
    if enc_cfg.labels:
        fwd += 2 * enc_cfg.num_cls_tokens * enc_cfg.dim * enc_cfg.labels
    return fwd * (3.0 if training else 1.0)


def lm_flops_per_token(cfg, seq: int, *, training: bool = True) -> float:
    """Matmul FLOPs one token of the sparse-expert language model
    (``models/lm.MlaMoeConfig``: each block's attention by its entry of
    ``cfg.kinds`` — latent, linear, or grouped-query, full and sliding)
    requires at sequence length ``seq``, on this chip's share: the experts,
    heads and vocabulary rows held. 2·m·n·k per matmul; the causal core
    counts its lower triangle once (mean context ``seq / 2``), a sliding
    layer's the keys its window shows (``min(i + 1, window)`` for query
    ``i``, so it does not grow with ``seq``); a linear-attention core counts
    the recurrence's three products with its (d_k, d_v) state, ``6 · d_k ·
    d_v`` a head, whatever ``seq`` and the chunking; a routed expert is
    counted for the share of (token, expert) pairs expected here (``k · held
    / experts``); a gated short-convolution block (kind ``conv``) counts its
    two projections, ``dim → 3 · dim`` and ``dim → dim``, and has no term
    that grows with ``seq``; the embedding is a lookup and the short
    convolutions, their gates and a q/k norm are elementwise; a tied head's
    product is counted as an untied one's (tying saves parameters, not
    products); backward = 2 x forward, recomputation not counted.

    A block-diffusion model (``cfg.diffusion_block`` = ``B`` > 0) runs a clean
    and a noisy copy of every sequence through the trunk, and a token here is
    a clean token, of which a sequence has ``seq``: its token-wise products
    (projections, router, experts) are counted twice, once a copy; the core
    over the ``seq² + seq · B`` entries the pattern shows (``seq + B`` keys a
    clean token, both copies' queries together); the head once, since it
    reads the noisy copy alone."""
    d, h = cfg.dim, cfg.heads
    copies = 2 if cfg.diffusion_block else 1
    qk, dv = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    query = d * h * qk if cfg.q_lora_rank is None else d * cfg.q_lora_rank + cfg.q_lora_rank * h * qk
    latent = 2 * (
        query
        + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + dv)
        + h * dv * d
        + (d * h if cfg.attn_gate else 0)
    )
    core = 2 * (seq / 2) * h * (qk + dv)
    hk, dh, rank = cfg.kda_heads or h, cfg.kda_head_dim, cfg.kda_gate_rank
    # a gate's projection to ``out`` columns: one matrix, or two through ``rank``
    gate_proj = lambda out: d * out if rank is None else d * rank + rank * out
    # q, k, v, W_o and beta; the decay gate and the output gate; the recurrence
    linear = (2 * (4 * d * hk * dh + d * hk + gate_proj(hk * dh)
                   + gate_proj(hk if cfg.kda_out_gate == "head" else hk * dh))
              + 6 * hk * dh * dh)
    gated = lambda hidden: 2 * 3 * d * hidden
    pairs_here = cfg.experts_per_token * cfg.held[1] / cfg.n_routed_experts
    sparse = (
        2 * d * cfg.n_routed_experts  # router
        + gated(cfg.shared_hidden)
        + pairs_here * gated(cfg.expert_hidden)
    )
    dense_layers = min(cfg.first_k_dense, cfg.layers)
    sparse_layers = cfg.layers - dense_layers + cfg.mtp_layers
    head = 2 * d * cfg.rows[1]

    def grouped_query(layer: int) -> float:
        hq, g, e = cfg.query_heads(layer), cfg.kv_heads, cfg.head_dim
        w = min(cfg.sliding_window, seq) if cfg.kinds[layer] == "sliding_attention" else seq
        keys = (w * (w + 1) / 2 + (seq - w) * w) / seq  # mean keys a query sees
        if cfg.diffusion_block:  # a clean token's two queries together
            keys = seq + cfg.diffusion_block
        gate = d * hq if cfg.attn_gate else 0
        return (copies * 2 * (d * hq * e + 2 * d * g * e + gate + hq * e * d)
                + 2 * keys * hq * 2 * e)

    kinds = cfg.kinds
    short_conv = 2 * (d * 3 * d + d * d)  # W_in and W_out
    fwd = (
        (kinds.count("mla") + cfg.mtp_layers) * (latent + core)
        + sum(grouped_query(i) for i, kind in enumerate(kinds)
              if kind not in ("mla", "kda", "conv"))
        + cfg.kda_layers * linear
        + kinds.count("conv") * short_conv
        + copies * dense_layers * gated(cfg.dense_hidden)
        + copies * sparse_layers * sparse
        + (1 + cfg.mtp_layers) * head
        + cfg.mtp_layers * 2 * (2 * d) * d  # W_eh
    )
    return fwd * (3.0 if training else 1.0)


def detect_peak_tflops() -> float | None:
    """Peak bf16 TFLOP/s of the current backend's first device, or None on
    the CPU backend — a CPU has no table entry, and a rate against a made-up
    peak is not a device metric. An accelerator whose kind is not in the
    table raises (:func:`lookup_peak_tflops`)."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return lookup_peak_tflops(dev.device_kind)


@dataclass
class MfuReport:
    images_per_sec: float
    flops_per_image: float
    achieved_tflops: float
    peak_tflops: float

    @property
    def mfu(self) -> float:
        return self.achieved_tflops / self.peak_tflops


def mfu_report(
    flops_per_image: float,
    images_per_sec_per_chip: float,
    *,
    peak_tflops: float,
) -> MfuReport:
    achieved = flops_per_image * images_per_sec_per_chip / 1e12
    return MfuReport(
        images_per_sec=images_per_sec_per_chip,
        flops_per_image=flops_per_image,
        achieved_tflops=achieved,
        peak_tflops=peak_tflops,
    )
