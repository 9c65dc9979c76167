"""Run the platform's main paths once on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the serve-replica phase only

One chip, in one process (a chip belongs to one process at a time):

  kernels   the Pallas kernels against their references at the widths the
            entry points use (paged decode Q=1 and Q=8 at qwen2-0.5b's
            widths in bf16, flash attention's output and q/k/v gradients
            at the train cell's 2 x 2048 tokens and qwen2-0.5b's heads,
            collision/TTC over 2048x8, one ICP step);
  serve     qwen2-0.5b at its published widths (random weights) as a
            ``serve`` job: Platform -> ServeDriver -> ContinuousBatchingEngine,
            8 requests x 128 prompt tokens x 32 greedy tokens, then again with
            speculation, the prefix cache and chunked prefill on;
  train     qwen2-0.5b through TrainDriver, 3 steps at batch 8 x 256;
  scenario  a 2050-scenario closed-loop sweep with and without the collision
            kernel, whose reports must agree;
  mapgen    HD-map generation with the ICP kernel (4 x 16 frames x 512 points).

``--chips 4`` runs a ``serve`` job with four replicas, one per chip, and
compares each request's greedy tokens with a one-replica run on one chip.

Any failed check raises, and the script exits non-zero without printing a
result.  On success the last line of stdout is one JSON object naming the
device, as JAX reports it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
TIMEOUT_S = 1100.0


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 2**30:.3f} GiB"


def run_job(platform, spec):
    from repro.platform import DONE

    report = platform.wait(platform.submit(spec), timeout_s=TIMEOUT_S)
    if report.state != DONE:
        raise RuntimeError(f"{spec.kind} job ended {report.state}: {report.error}")
    return report


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.collision.ops import collision_ttc
    from repro.kernels.collision.ref import TTC_MAX, collision_ttc_ref
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.flash_attention.ops import flash_attention, flash_attention_reference
    from repro.kernels.icp.ops import icp_step
    from repro.kernels.icp.ref import icp_step_ref

    # qwen2-0.5b: 14 query heads over 2 KV heads of width 64; 16-token pages
    B, Hq, Hkv, hd, page, n_pages = 8, 14, 2, 64, 16, 10
    P = B * n_pages
    lens = np.array([0, 1, 15, 16, 37, 90, 128, 151], np.int32)
    for Q in (1, 8):
        ks = jax.random.split(jax.random.PRNGKey(Q), 5)
        bf = jnp.bfloat16
        q = jax.random.normal(ks[0], (B, Q, Hq, hd), bf)
        kp = jax.random.normal(ks[1], (P + 1, page, Hkv, hd), bf)
        vp = jax.random.normal(ks[2], (P + 1, page, Hkv, hd), bf)
        kn = jax.random.normal(ks[3], (B, Q, Hkv, hd), bf)
        vn = jax.random.normal(ks[4], (B, Q, Hkv, hd), bf)
        bt = np.full((B, n_pages), P, np.int32)  # null page past the live ones
        for b in range(B):
            used = -(-(int(lens[b]) + Q) // page)
            bt[b, :used] = np.arange(b * n_pages, b * n_pages + used)
        args = (q, kp, vp, kn, vn, jnp.asarray(bt), jnp.asarray(lens))
        out = paged_decode_attention(*args, use_kernel=True)
        ref = paged_decode_attention(*args, use_kernel=False)
        diff = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
        # both round the softmax weights or the output to bf16 (8 mantissa
        # bits) on outputs of magnitude <= ~3: a few bf16 ulps apart
        tol = 3e-2
        if not diff <= tol:
            raise AssertionError(f"paged decode Q={Q}: max|kernel-ref| {diff} > {tol}")
        log(f"[kernels] paged_decode Q={Q} bf16 B={B} Hq={Hq} Hkv={Hkv} hd={hd} "
            f"page={page}: max|kernel-ref|={diff:.3g} (tol {tol}) PASS")

    # the train cell's attention: 2 x 2048 tokens, causal, forward and backward
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    S = 2048
    q = jax.random.normal(ks[0], (2, S, Hq, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, S, Hkv, hd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (2, S, Hq, hd), jnp.bfloat16)
    out, vjp = jax.vjp(jax.jit(flash_attention), q, k, v)
    ref, vjp_ref = jax.vjp(flash_attention_reference, q, k, v)
    # bf16 operands on the MXU with f32 accumulation against an f32 oracle
    # rounded to bf16: gaps of a few bf16 ulps of the largest entry
    tol = 2e-2
    for name, a, b in zip(("out", "dq", "dk", "dv"), (out, *vjp(ct)), (ref, *vjp_ref(ct))):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gap = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        if not gap <= tol:
            raise AssertionError(f"flash attention {name}: max|kernel-ref|/max|ref| {gap} > {tol}")
        log(f"[kernels] flash_attention {name} bf16 B=2 S={S} Hq={Hq} Hkv={Hkv} hd={hd} "
            f"causal: max|kernel-ref|/max|ref|={gap:.3g} (tol {tol}) PASS")

    S, A = 2048, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    ego_pos = jax.random.uniform(ks[0], (S, 2), minval=-50.0, maxval=50.0)
    ego_vel = jax.random.uniform(ks[1], (S, 2), minval=-15.0, maxval=15.0)
    ego_r = jax.random.uniform(ks[2], (S,), minval=1.0, maxval=2.5)
    ag_pos = ego_pos[:, None] + jax.random.uniform(ks[3], (S, A, 2), minval=-40.0, maxval=40.0)
    ag_vel = jax.random.uniform(ks[4], (S, A, 2), minval=-15.0, maxval=15.0)
    ag_r = jax.random.uniform(ks[5], (S, A), minval=0.3, maxval=2.5)
    cargs = (ego_pos, ego_vel, ego_r, ag_pos, ag_vel, ag_r)
    d, t, h = (np.asarray(x) for x in collision_ttc(*cargs))
    dr, tr, hr = (np.asarray(x) for x in collision_ttc_ref(*cargs))
    finite = tr < TTC_MAX
    # f32 elementwise math in both; TTC is a root of a quadratic whose
    # cancellation costs a few ulps of b/a (seconds at these speeds)
    if not (np.array_equal(h, hr) and np.array_equal(t < TTC_MAX, finite)):
        raise AssertionError("collision flags or TTC horizon differ from the reference")
    ddist = float(np.max(np.abs(d - dr)))
    dttc = float(np.max(np.abs(t[finite] - tr[finite]), initial=0.0))
    if not (ddist <= 1e-3 and dttc <= 1e-3):
        raise AssertionError(f"collision: max|dist diff| {ddist}, max|ttc diff| {dttc} > 1e-3")
    log(f"[kernels] collision_ttc S={S} A={A}: hits={int(h.sum())} equal, "
        f"max|dist diff|={ddist:.3g} m, max|ttc diff|={dttc:.3g} s (tol 1e-3) PASS")

    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    src = jax.random.uniform(ks[0], (512, 3), minval=-40.0, maxval=40.0)
    ang = 0.05
    rot = jnp.array([[np.cos(ang), -np.sin(ang), 0.0], [np.sin(ang), np.cos(ang), 0.0],
                     [0.0, 0.0, 1.0]], jnp.float32)
    tgt = src @ rot.T + jnp.array([0.4, -0.3, 0.05]) \
        + 0.01 * jax.random.normal(ks[1], (512, 3))
    R, tv, err = icp_step(src, tgt)
    Rr, tvr, errr = icp_step_ref(src, tgt)
    dR = float(jnp.max(jnp.abs(R - Rr)))
    dt_ = float(jnp.max(jnp.abs(tv - tvr)))
    # the kernel's distances come from |s|^2+|t|^2-2s.t in f32: the same
    # nearest neighbours, so the rigid solve agrees to f32 rounding
    if not (dR <= 1e-4 and dt_ <= 1e-3 and bool(jnp.isfinite(err))):
        raise AssertionError(f"icp_step: max|dR| {dR}, max|dt| {dt_}, err {err}")
    log(f"[kernels] icp_step 512 points: max|dR|={dR:.3g}, max|dt|={dt_:.3g} m, "
        f"err={float(err):.4g} vs ref {float(errr):.4g} PASS")


def serve_spec(devices: int = 1, replicas: int = 1, **fast):
    from repro.platform import JobSpec, ServeJobConfig

    return JobSpec(
        kind="serve", devices=devices, elastic=False,
        config=ServeJobConfig(
            arch=ARCH, scale="full", engine="continuous", batch=8,
            prompt_len=128, gen=32, temperature=0.0, replicas=replicas, **fast,
        ),
    )


def serve_tokens(report) -> dict:
    toks = report.metrics["_request_tokens"]
    if sorted(toks) != list(range(8)) or any(len(v) != 32 for v in toks.values()):
        raise AssertionError(f"serve: expected 8 requests x 32 tokens, got "
                             f"{ {k: len(v) for k, v in toks.items()} }")
    return toks


def phase_serve(platform, dev) -> None:
    t0 = time.perf_counter()
    base = serve_tokens(run_job(platform, serve_spec()))
    log(f"[serve] {ARCH} full width, 8 requests x 128 prompt x 32 greedy: DONE, "
        f"8x32 tokens ({time.perf_counter() - t0:.1f} s incl. compile) PASS")
    t0 = time.perf_counter()
    fast = serve_tokens(run_job(
        platform, serve_spec(spec_k=4, prefix_cache=True, prefill_chunk=16)))
    same = sum(a == b for r in base for a, b in zip(base[r], fast[r]))
    log(f"[serve] spec_k=4 prefix_cache prefill_chunk=16: DONE "
        f"({time.perf_counter() - t0:.1f} s); token agreement with flags off "
        f"{same}/256")
    log(f"[serve] peak_bytes_in_use {peak_gib(dev)} PASS")


def phase_train(platform, dev) -> None:
    from repro.platform import JobSpec, TrainJobConfig

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        report = run_job(platform, JobSpec(
            kind="train", devices=1, elastic=False,
            config=TrainJobConfig(
                arch=ARCH, scale="full", steps=3, batch=8, seq=256,
                ckpt_dir=ckpt, ckpt_every=50, log_every=1,
            ),
        ))
    loss = report.metrics["final_loss"]
    if not (report.metrics["steps"] == 3 and loss == loss and abs(loss) < float("inf")):
        raise AssertionError(f"train: {report.metrics}")
    log(f"[train] {ARCH} full width, 3 steps batch 8 x 256: DONE, loss {loss:.4f} "
        f"({time.perf_counter() - t0:.1f} s incl. compile and save) "
        f"peak_bytes_in_use {peak_gib(dev)} PASS")


def phase_scenario(platform) -> None:
    import numpy as np

    from repro.platform import JobSpec, ScenarioJobConfig, aggregate_scenario_metrics

    reports = {}
    for pallas in (False, True):
        t0 = time.perf_counter()
        r = run_job(platform, JobSpec(
            kind="scenario", devices=1,
            config=ScenarioJobConfig(per_family=410, use_pallas=pallas),
        ))
        reports[pallas] = (r.metrics, aggregate_scenario_metrics(
            [r.metrics], time.perf_counter() - t0))
    (m_ref, rep_ref), (m_k, rep_k) = reports[False], reports[True]
    for name, fs in rep_ref.families.items():
        if rep_k.families[name].collisions != fs.collisions:
            raise AssertionError(f"scenario {name}: collisions "
                                 f"{rep_k.families[name].collisions} != {fs.collisions}")
    ttc_k = np.asarray(m_k["_rollout"].min_ttc)
    ttc_r = np.asarray(m_ref["_rollout"].min_ttc)
    # same closed loop, same f32 math: TTC to f32 rounding of the quadratic
    dttc = float(np.max(np.abs(ttc_k - ttc_r) / np.maximum(1.0, np.abs(ttc_r))))
    if not dttc <= 1e-3:
        raise AssertionError(f"scenario: max relative min-TTC difference {dttc}")
    n_col = sum(fs.collisions for fs in rep_k.families.values())
    log(f"[scenario] {rep_k.scenarios} scenarios x {rep_k.steps} steps, collision "
        f"kernel on/off: collisions {n_col} equal in every family, max rel "
        f"min-TTC diff {dttc:.3g} (tol 1e-3) PASS")


def phase_mapgen(platform) -> None:
    from repro.platform import JobSpec, MapGenJobConfig

    t0 = time.perf_counter()
    r = run_job(platform, JobSpec(
        kind="mapgen", devices=1,
        config=MapGenJobConfig(partitions=4, frames=16, lidar_points=512),
    ))
    log(f"[mapgen] ICP kernel, 4 partitions x 16 frames x 512 points: DONE, "
        f"pose error {r.metrics['pose_error_m']:.3f} m, occupied cells "
        f"{r.metrics['occupied_cells']} ({time.perf_counter() - t0:.1f} s) PASS")


def phase_replicas(devices) -> None:
    from repro.platform import Platform

    n = len(devices)
    one = serve_tokens(run_job(Platform(total_devices=n), serve_spec()))
    log("[replicas] one replica on one chip: DONE, 8x32 tokens")
    four = serve_tokens(run_job(
        Platform(total_devices=n), serve_spec(devices=n, replicas=n)))
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"[replicas] {d}: bytes_in_use {stats.get('bytes_in_use', 0)} "
            f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 0)}")
    diverged = {
        r: next(i for i, (a, b) in enumerate(zip(one[r], four[r])) if a != b)
        for r in one if one[r] != four[r]
    }
    for r, i in sorted(diverged.items()):
        log(f"[replicas] request {r} diverges at token {i}: "
            f"{one[r][i]} (one replica) vs {four[r][i]} ({n} replicas)")
    if diverged:
        raise AssertionError(f"{len(diverged)} of 8 requests differ across replicas")
    log(f"[replicas] {n} replicas, one per chip: DONE, all 8 requests' greedy "
        f"tokens equal the one-replica run PASS")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import enable_compilation_cache

    cache = enable_compilation_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} compile cache {cache}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    devices = devices[: args.chips]

    t0 = time.perf_counter()
    if args.chips > 1:
        phase_replicas(devices)
    else:
        from repro.platform import Platform

        platform = Platform(total_devices=jax.device_count())
        for phase in (phase_kernels, lambda: phase_serve(platform, dev),
                      lambda: phase_train(platform, dev),
                      lambda: phase_scenario(platform),
                      lambda: phase_mapgen(platform)):
            phase()
            gc.collect()  # engines hold reference cycles; free their HBM now
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
