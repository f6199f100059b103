"""Roofline cost model: per-stage FLOPs / bytes for an architecture cell.

Task weights for the HVLB_CC placement are stage *compute volumes* (FLOPs);
edge volumes are activation bytes crossing stage boundaries; processor
execution rates are slice FLOP/s — the paper's ``comp = w / mu`` (Eq. 1)
becomes ``time = FLOPs / (chips * peak * mfu)``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

from repro.configs.base import ModelConfig, ShapeConfig


@dataclasses.dataclass(frozen=True)
class HW:
    """TPU v5e-like constants (per chip / per link)."""
    peak_flops: float = 197e12          # bf16
    hbm_bw: float = 819e9               # bytes/s
    ici_bw: float = 50e9                # bytes/s per link
    ici_links: int = 4
    dcn_bw: float = 6.25e9              # bytes/s per host cross-pod
    mfu: float = 0.5                    # assumed sustained fraction


def _attn_flops(cfg: ModelConfig, tokens: int, kv_len: int) -> float:
    H, dh = cfg.n_heads, cfg.head_dim
    K = cfg.n_kv_heads
    D = cfg.d_model
    proj = 2 * tokens * D * (H * dh) * 2 + 2 * tokens * D * (K * dh) * 2
    scores = 2 * tokens * kv_len * H * dh * 2        # qk + av
    return proj + scores


def _mlp_flops(cfg: ModelConfig, tokens: int) -> float:
    mult = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    return 2 * tokens * cfg.d_model * cfg.d_ff * mult


def ep_expert_flops(cfg: ModelConfig, routed: int) -> float:
    """Prefill FLOPs of the expert MLPs over ``routed`` (token, expert)
    pairs: one expert task's, or a whole MoE layer's."""
    mult = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    return 2 * routed * cfg.d_model * cfg.d_ff * mult


def _router_flops(cfg: ModelConfig, tokens: int) -> float:
    return 2 * tokens * cfg.d_model * cfg.n_experts


def _moe_flops(cfg: ModelConfig, tokens: int) -> float:
    routed = tokens * cfg.top_k
    return ep_expert_flops(cfg, routed) + _router_flops(cfg, tokens)


def ep_attention_flops(cfg: ModelConfig, tokens: int, kv_len: int) -> float:
    """Prefill FLOPs of one expert-parallel attention task: attention
    over ``tokens`` queries against ``kv_len`` keys, plus the router."""
    return _attn_flops(cfg, tokens, kv_len) + _router_flops(cfg, tokens)


def ep_message_bytes(cfg: ModelConfig, tokens: int) -> float:
    """Bytes of ``tokens`` activations in bf16 (a dispatch or combine
    message)."""
    return float(tokens * cfg.d_model * 2)


def ep_expert_weight_bytes(cfg: ModelConfig) -> float:
    """Bytes of one expert's MLP weights of one layer at int8 (the
    weights an expert task off its home chip streams in)."""
    mult = 3 if cfg.mlp in ("swiglu", "geglu") else 2
    return float(mult * cfg.d_model * cfg.d_ff)


def ep_kv_bytes(cfg: ModelConfig, kv_len: int) -> float:
    """Bytes of one layer's KV cache over ``kv_len`` tokens in bf16 (the
    cache an attention task off its rank's chip reads and writes back)."""
    return float(kv_len * 2 * cfg.n_kv_heads * cfg.head_dim * 2)


def _mamba1_flops(cfg: ModelConfig, tokens: int) -> float:
    D, Di, N, R = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    proj = 2 * tokens * D * 2 * Di + 2 * tokens * Di * D
    lowrank = 2 * tokens * Di * (R + 2 * N) + 2 * tokens * R * Di
    scan = tokens * Di * N * 6                      # recurrence+readout
    return proj + lowrank + scan


def _mamba2_flops(cfg: ModelConfig, tokens: int) -> float:
    D, Di, N = cfg.d_model, cfg.d_inner, cfg.d_state
    Hs = cfg.n_ssm_heads
    proj = 2 * tokens * D * (2 * Di + 2 * N + Hs) + 2 * tokens * Di * D
    chunk = 256
    ssd = (2 * tokens * chunk * N            # C B^T scores
           + 2 * tokens * chunk * cfg.ssm_head_dim * Hs / max(Hs, 1)
           + 6 * tokens * Di * N / chunk)
    return proj + ssd * Hs


def layer_costs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, float]:
    """FLOPs per single layer/block and activation bytes per boundary."""
    if shape.kind == "decode":
        tokens = shape.global_batch                  # one token per seq
        kv_len = shape.seq_len
    else:
        tokens = shape.global_batch * shape.seq_len
        kv_len = shape.seq_len
    act_bytes = tokens * cfg.d_model * 2             # bf16 boundary tensor
    out: Dict[str, float] = {"act_bytes": float(act_bytes)}
    if cfg.family in ("dense", "vlm", "audio"):
        out["block"] = _attn_flops(cfg, tokens, kv_len) + _mlp_flops(cfg, tokens)
    elif cfg.family == "moe":
        out["block"] = _attn_flops(cfg, tokens, kv_len) + _moe_flops(cfg, tokens)
    elif cfg.family == "ssm":
        out["block"] = _mamba1_flops(cfg, tokens)
    elif cfg.family == "hybrid":
        out["block"] = _mamba2_flops(cfg, tokens)
        out["shared_attn"] = (_attn_flops(cfg, tokens, kv_len) +
                              _mlp_flops(cfg, tokens))
    emb = 2 * tokens * cfg.d_model * cfg.vocab
    out["embed"] = 2 * tokens * cfg.d_model          # table lookup ~ O(T*D)
    out["head"] = float(emb)
    if shape.kind == "train":
        # backward ~ 2x forward for matmul-dominated blocks
        for k in ("block", "shared_attn", "head"):
            if k in out:
                out[k] = out[k] * 3.0
    return out


def total_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic whole-step FLOPs (global, all chips).

    Primary source for the roofline compute term: XLA's cost_analysis
    counts ``while`` (scan) bodies ONCE regardless of trip count, so the
    compiled number underestimates by ~n_layers x (verified in
    EXPERIMENTS.md §Dry-run).
    """
    c = layer_costs(cfg, shape)
    L = cfg.n_layers
    f = c["block"] * L + c["embed"] + c["head"]
    if cfg.family == "hybrid" and cfg.attn_every:
        f += c["shared_attn"] * (L // cfg.attn_every)
    return float(f)


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """The 6·N·D / 2·N·D convention (N = active params, D = tokens)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token/seq


def hbm_bytes(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic whole-step HBM traffic (global bytes, all chips).

    Weights: fp32 master read + bf16 cast write/read fwd+bwd, grad write,
    two Adam moments read+write.  Activations: layer boundary tensors plus
    recompute traffic under remat.  Decode: params + full cache sweep.
    """
    from repro.models.params import param_bytes as _pb
    pb = float(_pb(cfg))                          # fp32 master bytes
    D, L, V = cfg.d_model, cfg.n_layers, cfg.vocab
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        weight_traffic = pb * (2 + 1 + 4) + pb / 2 * 2   # masters+adam+bf16
        act_traffic = L * tokens * D * 2 * 8             # carry+internals
        head_traffic = tokens * V * 4 * 3                # logits fwd+bwd
        return weight_traffic + act_traffic + head_traffic
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        weight_traffic = pb / 2                          # bf16 read once
        act_traffic = L * tokens * D * 2 * 4
        kv_traffic = (L * tokens * cfg.n_kv_heads * cfg.head_dim * 2 * 2
                      if cfg.has_attention else 0)
        return weight_traffic + act_traffic + tokens * V * 4 + kv_traffic
    # decode
    B, S = shape.global_batch, shape.seq_len
    weight_traffic = pb / 2
    if cfg.family in ("dense", "vlm", "moe"):
        cache = 2 * L * B * S * cfg.n_kv_heads * cfg.head_dim * 2
    elif cfg.family == "ssm":
        cache = L * B * cfg.d_inner * cfg.d_state * 4 * 2
    else:                                               # hybrid
        G = L // cfg.attn_every
        cache = (2 * G * B * S * cfg.n_kv_heads * cfg.head_dim * 2 +
                 L * B * cfg.n_ssm_heads * cfg.ssm_head_dim *
                 cfg.d_state * 4 * 2)
    return weight_traffic + cache + B * V * 4


def stage_graph_costs(cfg: ModelConfig, shape: ShapeConfig,
                      n_stage_units: int = 16) -> Tuple[List[float], float]:
    """Collapse the layer chain into ~n_stage_units stage weights (FLOPs)
    plus the boundary activation bytes."""
    c = layer_costs(cfg, shape)
    L = cfg.n_layers
    per_unit = max(1, L // n_stage_units)
    units: List[float] = []
    i = 0
    while i < L:
        span = min(per_unit, L - i)
        w = c["block"] * span
        if cfg.family == "hybrid" and cfg.attn_every:
            n_shared = sum(1 for j in range(i, i + span)
                           if (j + 1) % cfg.attn_every == 0)
            w += c["shared_attn"] * n_shared
        units.append(w)
        i += span
    return units, c["act_bytes"]
