"""Roofline analysis from the dry-run's compiled artifacts (§Roofline).

Three terms per (arch x shape x mesh) cell, in seconds per step per device,
from the device's published peaks (`DEVICE_PEAKS`; TPU v5e below):

  compute    = FLOPs/device / 197 TFLOP/s (bf16)
  memory     = HBM bytes/device / 819 GB/s
  collective = link bytes/device / 50 GB/s

FLOPs and HBM bytes use an *analytic* workload model (matmul-exact, the
same arithmetic MFU papers use) because XLA's ``cost_analysis()`` counts a
``lax.scan`` body once rather than x trip-count — the raw HLO number is
reported alongside as a cross-check. Collective bytes ARE taken from the
compiled HLO (launch/hlo_stats.py), with while-loop trip scaling applied.

The memory term is strategy-aware: under TP each model-column rank
processes ALL tokens of its data column (weights sharded /tp, activations
x tp); under DP-ZeRO the weights are read in full per chip (gather +
stream) but activations shard /chips.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per the assignment; the
ratio MODEL_FLOPS / total-compiled-compute exposes remat recompute, GShard
dispatch overhead, expert padding and KV-replication waste.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Optional

from repro.configs import SHAPES, Shape, get_config
from repro.models import analysis
from repro.models.analysis import (active_param_count, family_counts, pad16,
                                   param_count, param_dtype_bytes)
from repro.models.config import ModelConfig

#: Published per-chip peaks, keyed by `jax.Device.device_kind`. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s of
#: HBM bandwidth, 1,600 Gbit/s of interconnect over four 50 GB/s links.
DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
#: the chip the dry-run and the event-step model are sized for
REFERENCE_DEVICE_KIND = "TPU v5 lite"


def device_peaks(device_kind: str) -> dict:
    """The published peaks of `device_kind`; an unlisted device raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to DEVICE_PEAKS with their source") from None

RESULTS = os.path.join(os.path.dirname(__file__), "results")


# --------------------------------------------------------------- FLOPs

def _attn_flops(cfg: ModelConfig, B: int, S: int, T: int, causal: bool,
                window: int, n_attn_layers: int) -> float:
    eff = min(T, window) if window else T
    if causal and not window and S == T:
        eff = T / 2                                   # causal triangle
    return 4.0 * B * S * eff * cfg.n_heads * cfg.hd * n_attn_layers


def fwd_flops(cfg: ModelConfig, B: int, S: int, expert_pad: int = 0,
              with_loss: bool = True) -> dict:
    """Forward FLOPs breakdown (global)."""
    d, hd, H, KV = cfg.d_model, cfg.hd, cfg.n_heads, cfg.n_kv_heads
    tok = B * S
    br = {}
    n_attn, n_rec, n_m, n_s = family_counts(cfg)

    if cfg.family == "encdec":
        ne, nd = cfg.n_enc_layers, cfg.n_dec_layers
        qkv = 2 * d * (H + 2 * KV) * hd
        br["attn_proj"] = tok * (qkv + 2 * d * H * hd) * (ne + nd)
        br["xattn_proj"] = tok * (qkv + 2 * d * H * hd) * nd
        br["attn"] = (_attn_flops(cfg, B, S, S, False, 0, ne) +
                      _attn_flops(cfg, B, S, S, True, 0, nd) +
                      _attn_flops(cfg, B, S, S, False, 0, nd))
        ff_mult = 4 if cfg.mlp_type == "gelu" else 6
        br["mlp"] = tok * ff_mult * d * cfg.d_ff * (ne + nd)
    elif cfg.family == "ssm":
        from repro.models.xlstm import _slstm_ff
        di = 2 * d
        dh = di // cfg.n_heads
        per_m = 2 * d * 2 * di + 6 * di * dh + 2 * di * d
        chunk = cfg.mlstm_chunk
        per_m_cell = 4 * cfg.n_heads * chunk * dh + 6 * cfg.n_heads * dh * dh
        br["mlstm"] = tok * (per_m + per_m_cell) * n_m
        dhs = d // cfg.n_heads
        per_s = 2 * d * 4 * d + 2 * cfg.n_heads * dhs * 4 * dhs + \
            6 * d * _slstm_ff(d)
        br["slstm"] = tok * per_s * n_s
    else:
        dr = cfg.d_rnn or d
        if n_attn:
            qkv = 2 * d * (H + 2 * KV) * hd
            br["attn_proj"] = tok * (qkv + 2 * d * H * hd) * n_attn
            br["attn"] = _attn_flops(cfg, B, S, S, True, cfg.local_window,
                                     n_attn)
        if n_rec:
            br["rglru"] = tok * (6 * d * dr + 4 * dr * dr + 10 * dr) * n_rec
        if cfg.n_experts:
            E = expert_pad or cfg.n_experts
            k = cfg.experts_per_token
            C = max(1, math.ceil(S * k / E * cfg.capacity_factor))
            br["router"] = tok * 2 * d * E * cfg.n_layers
            br["moe_dispatch"] = 2 * (2.0 * B * S * E * C * d) * cfg.n_layers
            br["experts"] = tok * k * 6 * d * cfg.expert_d_ff * cfg.n_layers
            par_ff = cfg.shared_expert_d_ff or (cfg.d_ff if
                                                cfg.dense_residual else 0)
            if par_ff:
                br["shared_mlp"] = tok * 6 * d * par_ff * cfg.n_layers
        else:
            ff_mult = 4 if cfg.mlp_type == "gelu" else 6
            br["mlp"] = tok * ff_mult * d * cfg.d_ff * cfg.n_layers
    if with_loss:
        br["unembed"] = tok * 2 * d * pad16(cfg.vocab_size)
    return br


def decode_flops(cfg: ModelConfig, B: int, T: int, kv_repeat: int,
                 expert_pad: int) -> dict:
    br = fwd_flops(cfg, B, 1, expert_pad, with_loss=True)
    n_attn, n_rec, n_m, n_s = family_counts(cfg)
    eff = min(T, cfg.local_window) if cfg.local_window else T
    if "attn" in br:
        br["attn"] = 4.0 * B * eff * cfg.n_heads * cfg.hd * n_attn
    if cfg.family == "encdec":
        from repro.models.encdec import MEMORY_LEN
        br["attn"] = 4.0 * B * (T + MEMORY_LEN) * cfg.n_heads * cfg.hd * \
            cfg.n_dec_layers
    return br


# --------------------------------------------------------------- HBM

def per_device_hbm(cfg: ModelConfig, shape: Shape, strategy: str,
                   kv_repeat: int, expert_pad: int, chips: int, tp: int,
                   dp: int, moment_bytes: int = 4) -> float:
    """Per-device HBM traffic per step (bytes), strategy-aware."""
    B, S = shape.batch, shape.seq
    bp = param_dtype_bytes(cfg)
    bc = 2 if cfg.compute_dtype == "bfloat16" else 4
    P = param_count(cfg, expert_pad)
    d, L = cfg.d_model, cfg.n_layers
    if shape.kind == "train":
        passes = 3.0 if cfg.remat == "full" else 2.0    # fwd(+refwd)+bwd
        if strategy == "dp_zero1":
            w = P * bp * (passes + 1)                    # + grad write
            opt = (4 * P * moment_bytes + 2 * P * bp) / dp
            tok_chip = B * S / chips
        elif strategy == "dp_zero3":
            w = P * bp * (passes + 1)                    # gathered stream
            opt = (4 * P * moment_bytes + 2 * P * bp) / chips
            tok_chip = B * S / chips
        else:                                            # tp
            w = P * bp * (passes + 1) / tp
            opt = (4 * P * moment_bytes + 2 * P * bp) / chips
            tok_chip = B * S / dp
        acts = 4.0 * tok_chip * d * L * bc
        return w + opt + acts
    if shape.kind == "prefill":
        tok_chip = B * S / dp
        return P * bp / tp + 2.0 * tok_chip * d * L * bc
    # decode: active params (sharded over model) + cache shard per chip
    n_attn, n_rec, n_m, n_s = family_counts(cfg)
    act = active_param_count(cfg) * bp / tp
    eff = min(S, cfg.local_window) if cfg.local_window else S
    kvr = cfg.n_kv_heads * kv_repeat
    cache = 2.0 * B * eff * kvr * cfg.hd * 2 * max(n_attn, 1) / chips
    if cfg.family == "ssm":
        di = 2 * d
        dh = di // cfg.n_heads
        cache = 2.0 * B * cfg.n_heads * dh * dh * 4 * n_m / chips
    if cfg.family == "encdec":
        cache = 2.0 * B * S * kvr * cfg.hd * 2 * cfg.n_dec_layers / chips
    return act + cache


# ------------------------------------------------- DES event-step model

def event_step_cost(n_jobs: int, n_types: int, ring: int,
                    dtype_bytes: int = 4, chaos: bool = False) -> dict:
    """Analytic bytes/event and flops/event for the fused DES event step.

    Models one lane-column of `repro.kernels.packet_step` (equivalently
    one `packet_scan_step` trip): the per-event working set is the
    23-column scan state — 12 scalars, 5 [H] per-type rows, 6 [ring]
    group-ring rows — read and written once per event, plus the workload
    gathers (prefix-sum rows at head/tail per type, the submit-time and
    job-type picks) that cannot stay resident because they index into
    [N]-sized arrays. Float work is a handful of elementwise ops per
    type row (`packet.queue_weights`) and per-event group math; with
    chaos, the outcome draw plus the fixed-trip `_credit_cut` binary
    search (ceil(log2(N+1)) gathers of one element each). Constants are
    deliberately coarse — the point of the model is the *ratio*: tens of
    bytes moved per float op puts the step deep in the memory-bound
    regime, which is the quantitative argument for keeping the ring
    state kernel-resident (VMEM) rather than bouncing it through HBM
    every `lax.scan` trip.
    """
    H, R = int(n_types), int(ring)
    state_elems = 12 + 5 * H + 6 * R
    state_bytes = 2 * state_elems * dtype_bytes          # read + write
    # prefw[tail] + prefw[head] per type row, submit/jtype/t_sub picks
    gathers = 2 * H + 6
    if chaos:
        gathers += max(int(n_jobs + 1).bit_length(), 1)  # _credit_cut
        gathers += 8                # uniforms, pool decode, remnant walk
    gather_bytes = gathers * dtype_bytes
    flops = 14 * H + 48 + (64 if chaos else 0)
    return {
        "n_jobs": int(n_jobs), "n_types": H, "ring": R,
        "dtype_bytes": int(dtype_bytes), "chaos": bool(chaos),
        "state_bytes_per_event": state_bytes,
        "gather_bytes_per_event": gather_bytes,
        "bytes_per_event": state_bytes + gather_bytes,
        "flops_per_event": flops,
    }


def event_step_roofline(n_jobs: int, n_types: int, ring: int,
                        n_lanes: int = 1, dtype_bytes: int = 4,
                        chaos: bool = False,
                        budget: int | None = None) -> dict:
    """Predicted ceiling for one DES experiment on the reference device.

    Applies the §Roofline terms to `event_step_cost`: a lane pays
    ``budget`` (~3N) events, each bounded below by max(bytes/hbm_bw,
    flops/peak_flops) with the byte traffic amortized over the `n_lanes`
    lanes of one dispatch (the flop term never binds — the step is
    hundreds of bytes per ~100 flops). ``predicted_ms_per_experiment``
    is what an HBM-resident scan step costs at the device's streaming
    bandwidth; a kernel that keeps the state columns VMEM-resident pays
    only the gather traffic, so the gap between the two predictions
    (``state_resident_ms_per_experiment``) is the headroom the Pallas
    event-step kernel chases. BENCH_des records both next to the
    measured engines.
    """
    peaks = device_peaks(REFERENCE_DEVICE_KIND)
    cost = event_step_cost(n_jobs, n_types, ring, dtype_bytes, chaos)
    ev = int(budget) if budget is not None else 3 * int(n_jobs)
    lanes = max(1, int(n_lanes))
    mem_s = ev * cost["bytes_per_event"] / peaks["hbm_bw"]
    flop_s = ev * cost["flops_per_event"] / peaks["peak_flops"]
    resident_s = ev * cost["gather_bytes_per_event"] / peaks["hbm_bw"]
    return {
        **cost,
        "events_per_lane": ev, "n_lanes": lanes,
        "bound": "memory" if mem_s >= flop_s else "compute",
        "predicted_ms_per_experiment": max(mem_s, flop_s) * 1e3,
        "state_resident_ms_per_experiment": max(resident_s, flop_s) * 1e3,
        "device_kind": REFERENCE_DEVICE_KIND,
        "peak_flops": peaks["peak_flops"], "hbm_bw": peaks["hbm_bw"],
    }


# --------------------------------------------------------------- terms

@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    strategy: str
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float
    total_flops: float
    hlo_flops_raw: float
    bound: str
    frac_of_roofline: float       # compute / sum(terms): achievable MFU
    notes: str = ""

    def table_row(self) -> str:
        return (f"| {self.arch} | {self.shape} | {self.mesh} | "
                f"{self.strategy} | "
                f"{self.compute_s * 1e3:.2f} | {self.memory_s * 1e3:.2f} | "
                f"{self.collective_s * 1e3:.2f} | {self.bound} | "
                f"{self.frac_of_roofline * 100:.1f}% | "
                f"{self.model_flops / max(self.total_flops, 1):.2f} |")


def analyze_record(rec: dict) -> Optional[Roofline]:
    if not rec.get("ok"):
        return None
    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    chips = rec["devices"]
    pol = rec["policy"]
    kvr = pol.get("kv_repeat", 1)
    epad = pol.get("expert_pad", 0)
    strategy = pol.get("strategy", "tp")
    tp = 16
    dp = chips // tp

    bpe = param_dtype_bytes(cfg)
    n_act = active_param_count(cfg)
    if shape.kind == "decode":
        br = decode_flops(cfg, shape.batch, shape.seq, kvr, epad)
        total = sum(br.values())
        model = 2.0 * n_act * shape.batch
    else:
        br = fwd_flops(cfg, shape.batch, shape.seq, epad,
                       with_loss=(shape.kind == "train"))
        fwd = sum(br.values())
        if shape.kind == "train":
            remat = 1.0 if cfg.remat == "full" else 0.0
            total = fwd * 3.0 + fwd * remat
            model = 6.0 * n_act * shape.batch * shape.seq
        else:
            total = fwd
            # prefill computes no logits: exclude the unembed params
            model = 2.0 * (n_act - pad16(cfg.vocab_size) * cfg.d_model) \
                * shape.batch * shape.seq

    mb = 2 if rec["arch"].startswith("arctic") else 4
    hbm = per_device_hbm(cfg, shape, strategy, kvr, epad, chips, tp, dp, mb)
    coll = rec.get("collectives", {}).get("link_bytes_per_device", 0.0)

    peaks = device_peaks(REFERENCE_DEVICE_KIND)
    compute_s = total / chips / peaks["peak_flops"]
    memory_s = hbm / peaks["hbm_bw"]
    collective_s = coll / peaks["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bound = max(terms, key=terms.get)
    frac = compute_s / max(sum(terms.values()), 1e-30)
    fix = _suggestion(bound, strategy, cfg, shape)
    return Roofline(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"], chips=chips,
        strategy=strategy,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model, total_flops=total,
        hlo_flops_raw=rec.get("flops", -1), bound=bound,
        frac_of_roofline=frac,
        notes="; ".join([fix] + pol.get("notes", [])))


def _suggestion(bound: str, strategy: str, cfg: ModelConfig,
                shape: Shape) -> str:
    """One sentence: what would move the dominant term down."""
    if bound == "compute":
        return ("fix: compute-bound — fuse attention/recurrence via the "
                "Pallas kernels; next win is arithmetic, not layout")
    if bound == "memory":
        if shape.kind == "decode":
            return ("fix: int8/fp8 KV-cache + wider decode batches to "
                    "amortize param streaming")
        return "fix: tighter remat policy / activation dtype"
    # collective-bound
    if cfg.n_experts and strategy in ("tp", "serve"):
        return ("fix: explicit shard_map all-to-all expert routing "
                "instead of SPMD-auto dispatch")
    if strategy == "dp_zero1":
        return ("fix: quantized (int8/fp8) gradient all-reduce; overlap "
                "bucketed reduce with backward compute")
    if strategy == "dp_zero3":
        return ("fix: overlap param gathers with compute (latency-hiding "
                "scheduler); ZeRO-1 if params fit HBM")
    if strategy == "dp_seq":
        return ("fix: ring-attention pipelining of the per-layer K/V "
                "gathers")
    if strategy == "serve":
        return ("fix: hierarchical (ICI-first) all-reduce; replicate "
                "small weights")
    return ("fix: sequence-parallel norms/residuals (halves TP "
            "activation all-reduces)")


def load_records(paths=None) -> list[dict]:
    paths = paths or [os.path.join(RESULTS, "dryrun.json"),
                      os.path.join(RESULTS, "dryrun_extra.json")]
    by_cell = {}
    for p in paths:
        if not os.path.exists(p):
            continue
        for r in json.load(open(p)):
            k = (r.get("arch"), r.get("shape"),
                 "multi" if (r.get("devices") == 512 or
                             "2x" in str(r.get("mesh"))) else "single")
            if k not in by_cell or r.get("ok"):
                by_cell[k] = r
    return list(by_cell.values())


def analyze_all(paths=None) -> list[Roofline]:
    rows = [analyze_record(r) for r in load_records(paths)]
    rows = [r for r in rows if r]
    rows.sort(key=lambda x: (x.arch, x.shape, x.mesh))
    return rows


HDR = ("| arch | shape | mesh | strategy | compute ms | memory ms | "
       "collective ms | bound | roofline frac | useful/total |")


def main():
    import sys
    paths = sys.argv[1:] or None
    rows = analyze_all(paths)
    print(HDR)
    print("|" + "---|" * 10)
    for row in rows:
        print(row.table_row())
    out = os.path.join(RESULTS, "roofline.json")
    with open(out, "w") as f:
        json.dump([dataclasses.asdict(r) for r in rows], f, indent=1)
    print(f"\n{len(rows)} cells analyzed -> {out}")


if __name__ == "__main__":
    main()
