"""Roofline analysis per (arch x shape) on the single-pod production mesh.

Retained LM-era sweep; the quadrature kernels this repo actually runs are
costed by :mod:`repro.perf.catalog` (``python -m benchmarks.run
--roofline``).  Three terms (seconds, PER DEVICE — the partitioned HLO is
the per-device program), sourced from a measured machine file when one
exists (:func:`resolve_terms`) and from the documented v5e preset below
otherwise:

    compute    = HLO_FLOPs / peak_flops       (preset: 197e12, v5e bf16)
    memory     = HLO_bytes_accessed / mem_bw  (preset: 819e9, HBM)
    collective = per-device collective payload bytes / ici_bw (preset: 50e9)

XLA's HloCostAnalysis counts a while/scan body ONCE regardless of trip
count, so costing the scanned-layers module directly undercounts by the
layer count.  Instead we lower two auxiliary modules per cell:

    P1 = model with ONE period of layers (scan trip count 1 — exact)
    P2 = model with TWO periods, the second unrolled into the prologue
         (scan trip count 1 + unrolled period — exact)

and extrapolate: total = cost(P1) + (n_periods - 1) * (cost(P2) - cost(P1)).
The marginal (P2 - P1) isolates exactly one period INCLUDING its
collectives; embed/head/loss/optimizer live in P1.  Memory comes from the
real dry-run compile (results/dryrun/*.json), not from the auxiliary
modules.

MODEL_FLOPS = 6 * N_active * tokens (train) or 2 * N_active * tokens
(+ attention terms) — the usefulness ratio MODEL_FLOPS / HLO_FLOPs catches
remat/redundancy waste.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os

# Documented v5e fallback preset — vendor-sheet numbers for the 256-chip
# production mesh this sweep was originally written against.  These are
# NOT this container's numbers: prefer a measured machine file
# (``python -m repro.perf.machine``), resolved via :func:`resolve_terms`
# below / the ``--machine`` flag.  The values are pinned to
# ``repro.perf.machine.PRESETS["TPU v5 lite"]`` by a drift test in
# ``tests/test_perf.py``.
PEAK_FLOPS = 197e12  # v5e bf16 peak per chip, FLOP/s
HBM_BW = 819e9  # v5e HBM bandwidth per chip, B/s
ICI_BW = 50e9  # v5e ICI per link, B/s
CHIPS = 256

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def resolve_terms(machine_path: str | None = None) -> tuple[float, float, float]:
    """(peak_flops, mem_bw, ici_bw) for the running device.

    Resolved by ``repro.perf.machine.resolve_machine``: a machine file
    measured on this device kind, else the preset for it.  A measured file
    with no inter-device probe
    (single device) keeps the preset ICI term so the collective column
    stays defined.
    """
    from repro.perf.machine import resolve_machine

    m = resolve_machine(machine_path)
    return (
        float(m["peak_flops"]),
        float(m["mem_bw"]),
        float(m["ici_bw"]) if m.get("ici_bw") else ICI_BW,
    )


def _cost_of(cfg, shape_name, mesh, microbatches, remat, rules=None):
    import jax

    from repro.launch.dryrun import build_cell, collective_bytes

    fn, args, shardings, donate = build_cell(
        cfg,
        shape_name,
        mesh,
        microbatches=microbatches,
        remat=remat,
        rules=rules,
    )
    compiled = (
        jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        .lower(*args)
        .compile()
    )
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": sum(v for k, v in coll.items() if k != "count"),
        "coll_by_kind": coll,
    }


def model_flops(cfg, shape) -> float:
    """Analytic useful-FLOPs for the cell (global, all chips)."""
    n_active = cfg.active_param_count()
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = b * s
        flops = 6.0 * n_active * tokens
        att = _attention_flops(cfg, b, s, causal=True)
        return flops + 3.0 * att  # fwd + 2x bwd
    if shape.kind == "prefill":
        tokens = b * s
        return 2.0 * n_active * tokens + _attention_flops(cfg, b, s, causal=True)
    # decode: one token, attention reads the whole cache
    flops = 2.0 * n_active * b
    att = _attention_decode_flops(cfg, b, s)
    return flops + att


def _cache_bytes(cfg, shape) -> float:
    """Global KV/latent/SSM cache bytes at full context (bf16)."""
    b, s = shape.global_batch, shape.seq_len
    total = 0.0
    for i in range(cfg.n_layers):
        if cfg.layer_kind(i) == "ssm":
            total += 2.0 * b * cfg.ssm_heads * cfg.ssm_headdim * cfg.ssm_state
            total += 2.0 * b * (cfg.ssm_conv - 1) * (cfg.d_inner + 2 * cfg.ssm_state)
        elif cfg.use_mla:
            total += 2.0 * b * s * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        else:
            total += 2.0 * b * s * 2 * cfg.n_kv_heads * cfg.resolved_head_dim
    return total


def _n_attn_layers(cfg) -> int:
    return sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn")


def _attention_flops(cfg, b, s, causal) -> float:
    la = _n_attn_layers(cfg)
    if la == 0:
        return 0.0
    if cfg.use_mla:
        d_eff = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        per = 4.0 * b * s * s * cfg.n_heads * d_eff
    else:
        hd = cfg.resolved_head_dim
        per = 4.0 * b * s * s * cfg.n_heads * hd
    if causal:
        per *= 0.5
    return per * la


def _attention_decode_flops(cfg, b, s_cache) -> float:
    la = _n_attn_layers(cfg)
    if la == 0:
        return 0.0
    if cfg.use_mla:
        d_eff = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        per = 4.0 * b * s_cache * cfg.n_heads * d_eff
    else:
        per = 4.0 * b * s_cache * cfg.n_heads * cfg.resolved_head_dim
    return per * la


def analyse_cell(
    arch: str,
    shape_name: str,
    *,
    microbatches: int = 8,
    remat: str = "full",
    dryrun_dir: str = "results/dryrun",
    rules=None,
    terms: tuple[float, float, float] | None = None,
):
    """Returns the roofline record for one cell on the (16,16) mesh."""
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.shapes import SHAPES, cell_status

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    skip = cell_status(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=False)
    period = cfg.block_pattern_period
    n_periods = (cfg.n_layers - cfg.first_k_dense) // period

    # P1: one period; P2: two periods with one unrolled in the prologue.
    cfg_p1 = dataclasses.replace(cfg, n_layers=period, first_k_dense=0)
    cfg_p2 = dataclasses.replace(cfg, n_layers=2 * period, first_k_dense=period)
    mb = microbatches if shape.kind == "train" else 1
    c1 = _cost_of(cfg_p1, shape_name, mesh, 1, remat, rules)
    c2 = _cost_of(cfg_p2, shape_name, mesh, 1, remat, rules)

    total = {
        k: c1[k] + (n_periods - 1) * (c2[k] - c1[k])
        for k in ("flops", "bytes", "coll")
    }
    # account for the real prologue (deepseek-v2's dense first layer ~ 1 period)
    if cfg.first_k_dense:
        total = {k: v + (c2[k] - c1[k]) for k, v in total.items()}

    # Chunked prefill wraps the layers in an n_chunks-trip scan that
    # HloCostAnalysis counts once — scale by the known trip count.
    if shape.kind == "prefill" and cfg.has_decode and shape.seq_len >= 8192:
        n_chunks = shape.seq_len // 4096
        total = {k: v * n_chunks for k, v in total.items()}

    if shape.kind == "decode":
        # Decode terms are computed ANALYTICALLY: the step reads the full
        # cache + the (bf16, fully sharded) weights exactly once per token,
        # which the HLO undercounts (the blockwise KV scan body is counted
        # once) and double-counts nothing.  This is the one shape where the
        # analytic model is exact rather than approximate.
        active_bytes = 2.0 * cfg.active_param_count() / CHIPS
        cache_bytes = _cache_bytes(cfg, shape) / CHIPS
        total["bytes"] = active_bytes + cache_bytes
        total["flops"] = model_flops(cfg, shape) / CHIPS

    peak_flops, hbm_bw, ici_bw = terms if terms is not None else (
        PEAK_FLOPS,
        HBM_BW,
        ICI_BW,
    )
    compute_t = total["flops"] / peak_flops
    memory_t = total["bytes"] / hbm_bw
    coll_t = total["coll"] / ici_bw
    bound = max(compute_t, memory_t, coll_t)
    dominant = (
        "compute"
        if bound == compute_t
        else ("memory" if bound == memory_t else "collective")
    )
    mf = model_flops(cfg, shape)
    hlo_flops_global = total["flops"] * CHIPS
    rec = {
        "arch": arch,
        "shape": shape_name,
        "status": "ok",
        "mesh": "16x16",
        "compute_s": compute_t,
        "memory_s": memory_t,
        "collective_s": coll_t,
        "dominant": dominant,
        "bound_s": bound,
        "roofline_frac": compute_t / bound if bound > 0 else 0.0,
        "hlo_flops_per_chip": total["flops"],
        "hlo_bytes_per_chip": total["bytes"],
        "coll_bytes_per_chip": total["coll"],
        "model_flops_global": mf,
        "useful_ratio": mf / hlo_flops_global if hlo_flops_global else 0.0,
        "marginal_per_period": {k: c2[k] - c1[k] for k in ("flops", "bytes", "coll")},
    }
    # attach dry-run memory if available
    tag = f"{arch}__{shape_name}__single.json"
    path = os.path.join(_HERE, dryrun_dir, tag)
    if os.path.exists(path):
        with open(path) as f:
            dr = json.load(f)
        rec["dryrun_temp_bytes"] = dr.get("temp_size_in_bytes")
        rec["dryrun_arg_bytes"] = dr.get("argument_size_in_bytes")
    return rec


def render_table(records) -> str:
    head = (
        "| arch | shape | compute (s) | memory (s) | collective (s) | dominant "
        "| useful FLOPs ratio | note |\n|---|---|---|---|---|---|---|---|"
    )
    rows = [head]
    for r in records:
        if r["status"] == "skip":
            rows.append(
                f"| {r['arch']} | {r['shape']} | — | — | — | — | — | {r['reason']} |"
            )
        else:
            rows.append(
                f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
                f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | "
                f"**{r['dominant']}** | {r['useful_ratio']:.2f} | "
                f"roofline frac {r['roofline_frac']:.2f} |"
            )
    return "\n".join(rows)


def main() -> None:
    # device-count flag must be set before jax init — mirror dryrun.py
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )
    from repro.configs import ARCH_IDS
    from repro.launch.shapes import SHAPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default="results/roofline")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--remat", default="full")
    ap.add_argument(
        "--machine",
        default=None,
        help="measured machine file for the roofline terms "
        "(default: results/perf/machine.json if present, else v5e preset)",
    )
    args = ap.parse_args()
    terms = resolve_terms(args.machine)

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    os.makedirs(args.out, exist_ok=True)
    records = []
    for arch in archs:
        for shape in shapes:
            path = os.path.join(args.out, f"{arch}__{shape}.json")
            if os.path.exists(path):
                with open(path) as f:
                    rec = json.load(f)
                records.append(rec)
                print(f"[cached] {arch} {shape}")
                continue
            try:
                rec = analyse_cell(
                    arch,
                    shape,
                    microbatches=args.microbatches,
                    remat=args.remat,
                    terms=terms,
                )
            except Exception as e:  # noqa: BLE001
                rec = {
                    "arch": arch,
                    "shape": shape,
                    "status": "fail",
                    "error": f"{type(e).__name__}: {e}"[:1500],
                }
            records.append(rec)
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            msg = (
                f"{rec.get('dominant', rec.get('reason', rec.get('error', '')))}"[:90]
            )
            print(f"[{rec['status']:4}] {arch:24} {shape:12} {msg}", flush=True)

    print()
    print(render_table(records))


if __name__ == "__main__":
    main()
