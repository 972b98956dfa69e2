"""Three-term roofline model against TPU v5e constants.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

HLO_FLOPs / HLO_bytes come from compiled.cost_analysis() (whole-program,
already per-partition under SPMD — we multiply back to global where
noted); collective_bytes from roofline.hlo. Scan bodies are counted once
by XLA — ``scan_correction`` rescales the dominant in-loop portion by the
recovered trip counts (see hlo.while_trip_counts); both raw and corrected
values are reported in EXPERIMENTS.md.

MODEL_FLOPS is the analytic 6·N·D (dense) / 6·N_active·D (MoE) useful-
work count; the ratio MODEL_FLOPS / HLO_FLOPs exposes remat/redundancy.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from repro.configs.base import ModelConfig, InputShape


@dataclasses.dataclass(frozen=True)
class HWConfig:
    name: str = "tpu_v5e"
    peak_flops: float = 197e12        # bf16 FLOP/s per chip
    hbm_bw: float = 819e9             # bytes/s per chip
    link_bw: float = 50e9             # bytes/s per ICI link
    hbm_bytes: float = 16e9           # HBM capacity per chip
    int_flops: float = 0.0            # int32 ALU op/s (0 -> use peak_flops)

    @property
    def peak_int_ops(self) -> float:
        """Peak int32 compare/select throughput for the sketch kernels.

        The sketch ingest is pure int32 (no MXU work), so its compute
        roof is the vector-ALU rate, not the bf16 matmul peak. Presets
        that know their int rate set ``int_flops``; others fall back to
        ``peak_flops`` (an optimistic roof — peak_fraction then under-
        reports, never over-reports).
        """
        return self.int_flops or self.peak_flops


# Published peaks per chip, keyed by the ``device_kind`` JAX reports
# (``repro.platform.hw_config`` looks the attached device up here). A
# device that is not in the table is an error, not a default: a peak
# fraction against another device's peaks is a wrong number.
#   TPU v5 lite (v5e): Google Cloud documentation, "TPU v5e" — 197
#             TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI;
#             int ~ one VPU lane-op per cycle (estimate, not published).
#   NVIDIA A100-SXM4-80GB: NVIDIA A100 datasheet — 312 TFLOP/s bf16,
#             2.0 TB/s HBM2e, 600 GB/s NVLink, 19.5 TOP/s int32.
HW_PRESETS: Dict[str, HWConfig] = {
    "TPU v5 lite": HWConfig(name="tpu_v5e", peak_flops=197e12,
                            hbm_bw=819e9, link_bw=50e9, hbm_bytes=16e9,
                            int_flops=4e12),
    "NVIDIA A100-SXM4-80GB": HWConfig(name="gpu_a100", peak_flops=312e12,
                                      hbm_bw=2.0e12, link_bw=600e9,
                                      hbm_bytes=80e9, int_flops=19.5e12),
}


def hw_for(kind: str) -> HWConfig:
    """The preset for one ``device_kind``; raises for any other kind."""
    try:
        return HW_PRESETS[kind]
    except KeyError:
        raise KeyError(
            f"no hardware preset for device_kind {kind!r}; "
            f"known kinds: {sorted(HW_PRESETS)}") from None


# Default for the transformer-side roofline terms below (the launch
# configs target v5e pods); sketch benches pass an explicit HWConfig
# resolved by repro.platform instead of this global.
HW = HW_PRESETS["TPU v5 lite"]


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    hlo_flops: float                  # global (all chips)
    hlo_bytes: float
    collective_bytes: float
    model_flops: float
    chips: int
    memory_s_analytic: float = 0.0    # TPU-expected (see analytic_hbm_bytes)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs over the roofline-bound time x peak — the score."""
        denom = self.bound_time_s * self.chips * HW.peak_flops
        return self.model_flops / denom if denom else 0.0

    @property
    def mfu_analytic(self) -> float:
        """MFU with the TPU-expected memory term in place of the
        fusion-inflated HLO bytes term (see analytic_hbm_bytes)."""
        bound = max(self.compute_s, self.memory_s_analytic, self.collective_s)
        denom = bound * self.chips * HW.peak_flops
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> Dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "memory_s_analytic": self.memory_s_analytic,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "mfu": self.mfu,
            "mfu_analytic": self.mfu_analytic,
            "chips": self.chips,
        }


def param_count(cfg: ModelConfig) -> Dict[str, float]:
    """Analytic parameter counts: total and per-token-active."""
    D, V, F = cfg.d_model, cfg.vocab_size, cfg.d_ff
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    pattern, n_periods, remainder = cfg.layer_pattern()
    kinds = list(pattern) * n_periods + list(remainder)

    attn = D * H * hd + 2 * D * KV * hd + H * hd * D
    mlp = (3 if cfg.mlp_gated else 2) * D * F
    moe_total = cfg.num_experts * (3 * D * F) + D * cfg.num_experts
    moe_active = cfg.experts_per_token * (3 * D * F) + D * cfg.num_experts

    mamba = 0.0
    if cfg.ssm_state:
        Din = cfg.ssm_expand * D
        nh = Din // cfg.ssm_head_dim
        conv_dim = Din + 2 * cfg.ssm_state
        mamba = (
            D * (2 * Din + 2 * cfg.ssm_state + nh)  # in_proj
            + 4 * conv_dim + conv_dim               # conv
            + 3 * nh + Din                          # A/dt/skip/norm
            + Din * D                               # out_proj
        )

    total = active = 0.0
    for kind in kinds:
        if kind == "mamba":
            total += mamba + D
            active += mamba + D
        elif kind == "mamba_attn":
            total += mamba + D
            active += mamba + D
            # shared block params counted once below
        else:
            ffn_t = moe_total if cfg.family == "moe" else mlp
            ffn_a = moe_active if cfg.family == "moe" else mlp
            total += attn + ffn_t + 2 * D
            active += attn + ffn_a + 2 * D
            if kind == "decoder_x":
                total += attn + D
                active += attn + D
    if cfg.family == "hybrid":
        shared = attn + mlp + 2 * D
        total += shared
        n_apps = sum(1 for k in kinds if k == "mamba_attn")
        active += shared * n_apps  # applied at every mamba_attn position
    if cfg.family == "encdec":
        enc = (attn + mlp + 2 * D) * cfg.encoder_layers
        total += enc
        active += enc
    emb = V * D * (1 if cfg.tie_embeddings else 2)
    total += emb + D
    active += emb + D
    return {"total": total, "active": active}


def model_flops(cfg: ModelConfig, shape: InputShape) -> float:
    """Analytic useful FLOPs for one step of this (arch x shape) cell.

    train: 6·N_active·tokens (fwd+bwd);  prefill: 2·N_active·tokens;
    decode: 2·N_active·batch (one token per sequence).
    Attention score/value FLOPs are added explicitly (they are not in N·D).
    """
    pc = param_count(cfg)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    base = mult * pc["active"] * tokens

    # attention matmul flops: 2 * 2 * S_eff * H * hd per token per layer
    H, hd = cfg.num_heads, cfg.resolved_head_dim
    pattern, n_periods, remainder = cfg.layer_pattern()
    kinds = list(pattern) * n_periods + list(remainder)
    attn_flops = 0.0
    for kind in kinds:
        if kind in ("full", "global", "decoder_x", "mamba_attn"):
            s_eff = shape.seq_len / 2 if shape.kind != "decode" else shape.seq_len
            if kind == "mamba_attn" and cfg.hh_kv_budget and shape.seq_len > 65536:
                s_eff = min(s_eff, cfg.hh_kv_budget)
            if kind == "global" and cfg.hh_kv_budget and shape.seq_len > 65536:
                s_eff = min(s_eff, cfg.hh_kv_budget)
        elif kind in ("swa", "local"):
            s_eff = min(cfg.window, shape.seq_len)
        else:  # mamba: SSD flops ~ chunked linear, fold into base
            continue
        per_token = 2 * 2 * s_eff * H * hd
        attn_flops += per_token * tokens * (mult / 2.0)
    return base + attn_flops


def analytic_hbm_bytes(cfg: ModelConfig, shape: InputShape,
                       microbatches: int = 1, remat: bool = True) -> float:
    """TPU-expected global HBM traffic per step (first-order model).

    The measured HLO bytes (cost_analysis on the CPU backend) count every
    instruction including fusion bodies — inflated ~10-100x over physical
    HBM traffic and insensitive to fusion-visible optimizations. This
    analytic estimate is reported alongside (EXPERIMENTS.md §Roofline
    'mem(anl)') and is what the §Perf memory-term decisions use:

      train:  weights x (fwd+bwd reads + grad write + opt r/w, xM for
              FSDP regathers) + activations x passes + attention probs
      decode: weights + KV caches (+ new-token writes)
      prefill: weights + activations + cache writes
    """
    pc = param_count(cfg)
    P = pc["active"] if shape.kind == "decode" else pc["total"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    D, L = cfg.d_model, cfg.num_layers

    # attention probs traffic (bf16): tokens x S_eff x heads, fwd(+bwd)
    H = max(cfg.num_heads, 1)
    pattern, n_periods, remainder = cfg.layer_pattern()
    kinds = list(pattern) * n_periods + list(remainder)
    probs = 0.0
    for kind in kinds:
        if kind in ("full", "global", "decoder_x", "mamba_attn"):
            s_eff = shape.seq_len / 2
        elif kind in ("swa", "local"):
            s_eff = min(cfg.window, shape.seq_len)
        else:
            continue
        probs += tokens * s_eff * H * 2

    if shape.kind == "train":
        passes = 3 if remat else 2                       # fwd + bwd (+refwd)
        w = P * 2 * (passes * microbatches)              # bf16 reads (FSDP regather/mb)
        w += P * 4 * 2 + P * 4 * 4 + P * 2               # grad f32 r/w, m/v r/w, cast
        acts = tokens * D * 2 * L * 8 * passes / (microbatches ** 0)  # ~8 tensors/layer
        return w + acts + probs * (2 if remat else 1) * 2
    if shape.kind == "prefill":
        acts = tokens * D * 2 * L * 6
        cache = tokens * cfg.num_kv_heads * cfg.resolved_head_dim * 2 * 2 * L
        return P * 2 + acts + probs + cache
    # decode: weights + cache read per token
    KV, hd = max(cfg.num_kv_heads, 1), cfg.resolved_head_dim
    cache = 0.0
    for kind in kinds:
        if kind in ("full", "global", "decoder_x", "mamba_attn"):
            c_len = shape.seq_len
            if cfg.hh_kv_budget and shape.seq_len > 65536:
                c_len = cfg.hh_kv_budget
        elif kind in ("swa", "local"):
            c_len = min(cfg.window, shape.seq_len)
        elif kind == "mamba":
            Din = cfg.ssm_expand * D
            cache += shape.global_batch * Din * cfg.ssm_state * 4 * 2
            continue
        else:
            continue
        cache += shape.global_batch * c_len * KV * hd * 2 * 2
    return P * 2 + cache + shape.global_batch * D * 2 * L * 6


def roofline_terms(
    *,
    hlo_flops_global: float,
    hlo_bytes_global: float,
    collective_bytes_global: float,
    chips: int,
    cfg: ModelConfig,
    shape: InputShape,
    microbatches: int = 1,
    remat: bool = True,
) -> RooflineTerms:
    return RooflineTerms(
        compute_s=hlo_flops_global / (chips * HW.peak_flops),
        memory_s=hlo_bytes_global / (chips * HW.hbm_bw),
        collective_s=collective_bytes_global / (chips * HW.link_bw),
        hlo_flops=hlo_flops_global,
        hlo_bytes=hlo_bytes_global,
        collective_bytes=collective_bytes_global,
        model_flops=model_flops(cfg, shape),
        chips=chips,
        memory_s_analytic=analytic_hbm_bytes(cfg, shape, microbatches, remat)
        / (chips * HW.hbm_bw),
    )


# ---------------------------------------------------------------------------
# Sketch-ingest cost model (SpaceSaving± bank kernel)
# ---------------------------------------------------------------------------
# First-order op counts per counter cell in the fused tiled kernel
# (DESIGN.md §14). These are compare/select/add counts read off the fused
# core bodies, not measured: sat_add is ~6 vector ops (two clamps + min +
# max + clip + add); fill/waterfill touch each cell ~12 times (masks,
# iota compare, two selects per array); one residual lockstep trip costs
# ~8 ops/cell (argmin tournament + one-hot select on three arrays).
_SAT_ADD_OPS = 6
_FILL_OPS = 12
_TOURNAMENT_OPS = 8


def sketch_ingest_cost(
    *,
    num_rows: int,
    k: int,
    block: int,
    lanes: int = 128,
    residual_trips: float = 0.0,
    dtype_bytes: int = 4,
) -> Dict[str, float]:
    """Analytic bytes/flops for one fused bank update of a (R, k) bank.

    bytes = bank tile traffic + block stream:
      - state tiles (ids/counts/errors) read + written once each:
        3 x R x k_pad x 4 x 2
      - block stream read once: the phase-1 delta tile (R x k_pad), the
        grouped residual layout (uids + nets, R x B each), and the raw
        item/weight block (B each)
    flops ~ compare/select ops: per-cell phase-1 + fill/waterfill work
    plus ``residual_trips`` lockstep tournament iterations, each a full
    (R x k_pad) argmin + one-hot select.

    ``residual_trips`` is the measured (or estimated) iteration count of
    the residual while-loop — 0 on a cold bank (bulk fill absorbs every
    insert), up to ~residual_frac x B on a saturated one.
    """
    k_pad = ((k + lanes - 1) // lanes) * lanes
    cells = num_rows * k_pad
    state_bytes = 3 * cells * dtype_bytes * 2
    stream_bytes = (
        cells * dtype_bytes                        # phase-1 delta tile
        + 2 * num_rows * block * dtype_bytes       # grouped uids + nets
        + 2 * block * dtype_bytes                  # raw items + weights
    )
    flops = cells * (_SAT_ADD_OPS + _FILL_OPS) \
        + residual_trips * cells * _TOURNAMENT_OPS
    return {"bytes": float(state_bytes + stream_bytes), "flops": float(flops)}


def sketch_roofline(cost: Dict[str, float], wall_s: float,
                    hw: Optional[HWConfig]) -> Dict[str, object]:
    """Roofline columns for one bench cell given its analytic cost.

    achieved_bytes_per_s — analytic bytes moved / measured wall time;
    peak_fraction        — achieved vs the preset's HBM bandwidth roof
                           (the sketch ingest is memory-bound at its
                           ~1.6 op/byte intensity on every preset);
    arith_intensity      — analytic flops / analytic bytes (op/byte).

    ``hw=None`` (no preset: a CPU run) leaves the device columns
    ``peak_fraction``/``bound_s``/``bound`` as "not measured" instead
    of reading a CPU time against some chip's peaks.
    """
    achieved = cost["bytes"] / wall_s if wall_s > 0 else 0.0
    out: Dict[str, object] = {
        "achieved_bytes_per_s": achieved,
        "arith_intensity": cost["flops"] / cost["bytes"] if cost["bytes"] else 0.0,
        "peak_fraction": "not measured",
        "bound_s": "not measured",
        "bound": "not measured",
    }
    if hw is not None:
        memory_s = cost["bytes"] / hw.hbm_bw
        compute_s = cost["flops"] / hw.peak_int_ops
        out.update(peak_fraction=achieved / hw.hbm_bw,
                   bound_s=max(memory_s, compute_s),
                   bound="memory" if memory_s >= compute_s else "compute")
    return out
