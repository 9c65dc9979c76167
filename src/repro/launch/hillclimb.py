import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf hillclimbing driver.

Runs one (arch x shape) cell with a named optimization variant (a policy /
rule override), writes the roofline JSON under a variant tag, and prints the
before/after delta on the three roofline terms — one
hypothesis->change->measure iteration per invocation.

    PYTHONPATH=src python -m repro.launch.hillclimb --arch qwen2-0.5b \
        --shape decode_32k --variant fewer_tp
"""

import argparse
import dataclasses
import json

from repro.config import ParallelConfig
from repro.launch import dryrun

# ---------------------------------------------------------------------------
# Variant registry: name -> (description, policy_override fn, extra_rules)
# ---------------------------------------------------------------------------


def _blocked_attention(model_cfg, pcfg):
    return dataclasses.replace(model_cfg, attention_impl="blocked"), pcfg


def _einsum_attention(model_cfg, pcfg):
    return dataclasses.replace(model_cfg, attention_impl="einsum"), pcfg


def _no_remat(model_cfg, pcfg):
    return dataclasses.replace(model_cfg, remat="none"), pcfg


def _remat_full(model_cfg, pcfg):
    return dataclasses.replace(model_cfg, remat="full"), pcfg


def _mb(n):
    def f(model_cfg, pcfg):
        return model_cfg, dataclasses.replace(pcfg, num_microbatches=n)

    return f


def _weights_2d(on: bool):
    def f(model_cfg, pcfg):
        return model_cfg, dataclasses.replace(pcfg, weights_2d=on)

    return f


def _bf16_scores(model_cfg, pcfg):
    return dataclasses.replace(model_cfg, dtype="bfloat16"), pcfg


def _moe_grouped(model_cfg, pcfg):
    assert model_cfg.moe is not None
    return (
        dataclasses.replace(
            model_cfg, moe=dataclasses.replace(model_cfg.moe, n_groups=16)
        ),
        pcfg,
    )


def _ssd_chunk(n):
    def f(model_cfg, pcfg):
        assert model_cfg.ssm is not None
        return (
            dataclasses.replace(
                model_cfg, ssm=dataclasses.replace(model_cfg.ssm, chunk_size=n)
            ),
            pcfg,
        )

    return f


VARIANTS = {
    "baseline": ("policy defaults", lambda m, p: (m, p), None),
    "blocked_attn": ("q-block chunked attention (never materialize SxS)", _blocked_attention, None),
    "einsum_attn": ("einsum attention everywhere (the default takes the flash kernel "
                    "for full self-attention on one TPU)", _einsum_attention, None),
    "no_remat": ("disable activation recompute", _no_remat, None),
    "remat_full": ("remat everything", _remat_full, None),
    "mb1": ("single microbatch", _mb(1), None),
    "mb2": ("2 microbatches", _mb(2), None),
    "mb4": ("4 microbatches", _mb(4), None),
    "mb8": ("8 microbatches", _mb(8), None),
    "mb16": ("16 microbatches", _mb(16), None),
    "weights2d_on": ("shard weight d_model over data (ZeRO-3-ish)", _weights_2d(True), None),
    "weights2d_off": ("replicate weights over data", _weights_2d(False), None),
    "moe_grouped": ("GShard grouped-local dispatch (G=16 aligned to data shards)",
                    _moe_grouped, {"moe_groups": "data", "moe_cap": None}),
    "bf16_scores": ("attention scores/softmax in bf16 (halves score traffic; "
                    "numerics flagged in EXPERIMENTS.md)",
                    lambda m, p: (dataclasses.replace(m, attn_scores_bf16=True), p), None),
    "hd_attn": ("decode attention contracts over the sharded head_dim; cache never moves",
                lambda m, p: (dataclasses.replace(m, attention_impl="hd_sharded"), p), None),
    "seq_shard_decode": ("flash-decoding style: KV cache sharded over sequence on the "
                         "model axis; softmax stats all-reduce, cache never moves",
                         lambda m, p: (m, p),
                         {"kv_seq": "model", "cache_heads": None, "cache_hd": None,
                          "act_heads": None}),
    "moe_pad_expert": ("pad experts 60->64 + expert-parallel + grouped dispatch",
                       lambda m, p: (dataclasses.replace(
                           m, moe=dataclasses.replace(
                               m.moe, pad_experts_to=64, shard_mode="expert", n_groups=16)), p),
                       {"moe_groups": "data", "moe_cap": None}),
    "ssd_chunk64": ("SSD chunk 64 (less intra-chunk quadratic work)", _ssd_chunk(64), None),
    "ssd_chunk128": ("SSD chunk 128", _ssd_chunk(128), None),
    # rule-level variants (extra_rules merged into the table)
    "seq_shard_model": ("shard activation seq over model axis (context parallel)",
                        lambda m, p: (m, p), {"seq": "model"}),
    "embed_data": ("shard embedding d_model over data", lambda m, p: (m, p), {"embed": "data"}),
    "vocab_data": ("shard vocab over data instead of model", lambda m, p: (m, p),
                   {"vocab": "data", "vocab_act": "data"}),
    "moe_cap_model": ("MoE capacity bins over model axis", lambda m, p: (m, p),
                      {"moe_cap": "model"}),
    "decode_batch_model": ("decode: shard batch over model too (no TP matmuls)",
                           lambda m, p: (m, p),
                           {"batch": ("pod", "data", "model"), "act_heads": None,
                            "heads": None, "ffn": None, "vocab": None, "vocab_act": None,
                            "cache_heads": None, "cache_hd": None, "act_ffn": None}),
}


def run_variant(arch: str, shape: str, variant: str, multi_pod: bool = False) -> dict:
    desc, override, extra_rules = VARIANTS[variant]
    print(f"### variant {variant}: {desc}")
    orig_lower = dryrun.lower_cell

    if extra_rules is not None:
        def patched(a, s, mp, extra_rules=None, depth=None, policy_override=None):
            merged = dict(VARIANTS[variant][2])
            if extra_rules:
                merged.update(extra_rules)
            return orig_lower(a, s, mp, extra_rules=merged, depth=depth,
                              policy_override=policy_override)

        dryrun.lower_cell = patched
    try:
        out = dryrun.run_cell(
            arch, shape, multi_pod,
            tag_suffix=f"__{variant}",
            policy_override=override,
        )
    finally:
        dryrun.lower_cell = orig_lower
    return out


def compare(arch: str, shape: str, variant: str) -> None:
    base_path = os.path.join(dryrun.OUT_DIR, f"{arch}__{shape}__pod1.json")
    var_path = os.path.join(dryrun.OUT_DIR, f"{arch}__{shape}__pod1__{variant}.json")
    if not (os.path.exists(base_path) and os.path.exists(var_path)):
        return
    b = json.load(open(base_path)).get("extrapolated", {})
    v = json.load(open(var_path)).get("extrapolated", {})
    if not b or not v:
        return
    print(f"\n=== {arch} x {shape}: baseline -> {variant} ===")
    for term in ("t_compute", "t_memory", "t_collective"):
        tb, tv = b[term], v[term]
        delta = (tv - tb) / tb * 100 if tb else float("inf")
        print(f"  {term:13s} {tb*1e3:10.2f}ms -> {tv*1e3:10.2f}ms  ({delta:+.1f}%)")
    db = max(b["t_compute"], b["t_memory"], b["t_collective"])
    dv = max(v["t_compute"], v["t_memory"], v["t_collective"])
    print(f"  dominant      {db*1e3:10.2f}ms -> {dv*1e3:10.2f}ms  ({(dv-db)/db*100:+.1f}%)")
    print(f"  useful_ratio  {b['useful_ratio']:.3f} -> {v['useful_ratio']:.3f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", required=True, choices=sorted(VARIANTS))
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    run_variant(args.arch, args.shape, args.variant, args.multi_pod)
    compare(args.arch, args.shape, args.variant)


if __name__ == "__main__":
    main()
