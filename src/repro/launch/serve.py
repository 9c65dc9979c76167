"""Serving CLI — thin wrapper over the unified platform API (paper §4.3).

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
        --batch 4 --prompt-len 64 --gen 32 [--engine continuous]

``--engine continuous`` serves the batch as individual requests through the
paged-KV continuous-batching engine (transformer families only) and reports
per-token latency percentiles next to throughput.  ``--replicas N`` fans the
tenant out over N engine replicas behind the join-shortest-queue router
(``repro.serving.router``).  ``--deadline-s`` attaches a per-request
latency budget (deadline-aware shed/degrade admission; with
``--hedge-threshold`` and ``--cells >= 2``, p99-at-risk requests are
hedged to a second cell, first win cancels the loser), and
``--predictive-autoscale`` scales replicas on the forecast arrival rate.
``--ckpt-dir`` serves the params of a previous ``launch.train`` run
instead of random init.  The engines live in
:class:`repro.platform.services.ServeDriver`.
"""

from __future__ import annotations

import argparse
import sys

from repro.compile_cache import enable_compilation_cache
from repro.platform import DONE, JobSpec, Platform, ServeJobConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", choices=["static", "continuous"], default="static")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=0,
                    help="decode slots per replica (0 = batch)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="continuous engine replicas behind the JSQ router")
    ap.add_argument("--cells", type=int, default=1,
                    help="serve cells (of --replicas engines each) behind "
                         "the pool-level cell router")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="per-cell autoscale ceiling on sustained queue "
                         "depth (0 disables)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request latency budget in seconds; requests "
                         "projected past it are degraded or shed "
                         "(0 disables)")
    ap.add_argument("--deadline-min-tokens", type=int, default=1,
                    help="degrade floor: shed rather than truncate below "
                         "this many generated tokens")
    ap.add_argument("--hedge-threshold", type=float, default=0.0,
                    help="hedge admitted requests projected past this "
                         "fraction of their budget to a second cell "
                         "(0 disables; needs --cells >= 2)")
    ap.add_argument("--predictive-autoscale", action="store_true",
                    help="scale replicas on the forecast arrival rate "
                         "instead of queue-depth hysteresis "
                         "(needs --max-replicas > --replicas)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to k tokens per "
                         "step from an n-gram proposer and verify them in "
                         "one decode (0 disables; greedy slots only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full prompt-prefix K/V pages across "
                         "requests (refcounted, copy-on-write tails)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="fold prefill into the decode step, at most this "
                         "many prompt tokens per step per slot (0 keeps "
                         "bucketed whole-prompt prefill)")
    ap.add_argument("--vocab", type=int, default=512, help="smoke-scale vocab")
    ap.add_argument("--seq", type=int, default=512,
                    help="smoke-scale max_seq_len (match the train job's "
                         "--seq when using --ckpt-dir)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="serve params from this train-job checkpoint dir")
    ap.add_argument("--pool-devices", type=int, default=8)
    ap.add_argument("--job-devices", type=int, default=2)
    ap.add_argument("--priority", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compilation_cache()

    spec = JobSpec(
        kind="serve",
        config=ServeJobConfig(
            arch=args.arch, scale=args.scale, batch=args.batch,
            prompt_len=args.prompt_len, gen=args.gen,
            temperature=args.temperature, seed=args.seed, engine=args.engine,
            page_size=args.page_size, slots=args.slots, replicas=args.replicas,
            cells=args.cells, max_replicas=args.max_replicas,
            deadline_s=args.deadline_s,
            deadline_min_tokens=args.deadline_min_tokens,
            hedge_threshold=args.hedge_threshold,
            predictive_autoscale=args.predictive_autoscale,
            spec_k=args.spec_k, prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk,
            vocab=args.vocab, seq=args.seq, ckpt_dir=args.ckpt_dir,
        ),
        devices=args.job_devices,
        priority=args.priority,
    )
    platform = Platform(total_devices=args.pool_devices)
    report = platform.wait(platform.submit(spec))
    print(report.summary())
    if report.state != DONE:
        sys.exit(1)


if __name__ == "__main__":
    main()
