"""Serve cells: one ``ContinuousBatchingEngine`` replica behind ``ServeRouter``,
driven by ``router.step(now)`` on the harness's clock.

Set-up builds the replica the way ``ServeDriver`` does, sizes its page pool
from the HBM left after the weights, warms every prefill bucket the mix can
reach and the decode step, and queues the mix's requests with their arrival
times; a backlog's first step, which fills every slot, is set-up too.  The
window then steps the router until ``seconds`` have passed.  The drain that
follows steps on, for at most the mix's ``drain_s``, until every request due
in the window has its first token (else it counts at its elapsed time) and
enough requests have finished for the check.  Each step's ``now`` maps to
the time the step returned, and every client-side time is taken from that.
"""

from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

import traffic as traffic_gen
import weights as weights_mod
from run_common import Compiles, Record, host_span, peak_bytes
from stats import returned_times

PAGE = 16
WARM_RID = 1 << 30  # warm-up requests, apart from the traffic's rids


def kv_bytes_per_page(model, dev) -> float:
    """Device bytes a page of the pool takes, measured: bytes in use before
    and after a probe pool is allocated."""
    import jax

    pages = 1024
    before = dev.memory_stats()["bytes_in_use"]
    probe = model.init_paged_state(pages + 1, PAGE)
    jax.block_until_ready(probe)
    after = dev.memory_stats()["bytes_in_use"]
    del probe
    return (after - before) / (pages + 1)


def program_temp_bytes(engine, model, num_pages: int, bucket: int, slots: int) -> int:
    """Temporaries of the engine's largest prefill and its decode step over a
    pool of ``num_pages``, from the compiler's ``memory_analysis()``."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    pages = jax.eval_shape(lambda: model.init_paged_state(num_pages + 1, PAGE))
    pre = engine._prefill.lower(
        engine.params, pages, sds((1, bucket), jnp.int32), np.int32(0),
        sds((bucket // PAGE,), jnp.int32), engine._prefill_key, np.float32(0),
    ).compile().memory_analysis()
    dec = engine._decode.lower(
        engine.params, pages, sds((slots,), jnp.int32),
        sds((slots, engine.max_pages_per_seq), jnp.int32), sds((slots,), jnp.int32),
        sds((slots,), jnp.bool_), sds((slots,), jnp.float32), engine._decode_key,
        np.int32(0),
    ).compile().memory_analysis()
    return max(pre.temp_size_in_bytes, dec.temp_size_in_bytes)


def size_pool(cfg: dict, mix: dict, dev, model, probe, max_len: int) -> dict:
    """The page pool takes the HBM left after what is in use (the weights),
    the compiled temporaries and the stated headroom.  The step programs'
    temporaries grow with the pool (each call copies it into another
    layout), so they are read from compiles at two pool sizes and solved
    for; slots follow from the mix's mean request."""
    per_page = kv_bytes_per_page(model, dev)
    bucket = max(warm_buckets(mix, max_len))
    slots_cap = cfg["serve"]["max_slots"]
    stats = dev.memory_stats()
    room = stats["bytes_limit"] - stats["bytes_in_use"] - cfg["serve"]["headroom_bytes"]
    p2 = int(0.4 * room / per_page)
    p1 = p2 // 2
    t1 = program_temp_bytes(probe, model, p1, bucket, slots_cap)
    t2 = program_temp_bytes(probe, model, p2, bucket, slots_cap)
    slope = (t2 - t1) / (p2 - p1)
    base = max(t1 - slope * p1, 0.0)
    pages = int((room - base) // (per_page + slope))
    slots = min(slots_cap, int(pages * PAGE // traffic_gen.mean_tokens(mix)))
    if pages < 1 or slots < 1:
        raise RuntimeError(f"no room for a page pool: {room} bytes")
    return {"num_pages": pages, "slots": slots, "pool_bytes_per_page": per_page,
            "temp_bytes_per_page": slope, "temp_bytes_base": base,
            "in_use_before_pool": stats["bytes_in_use"],
            "probe_temps": [[p1, t1], [p2, t2]]}


def warm_buckets(mix: dict, max_len: int) -> list[int]:
    from repro.serving.continuous import _bucket_len

    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    return sorted({_bucket_len(p, PAGE, max_len) for p in range(lo, hi + 1)})


def run(ctx) -> Record:
    import jax

    from repro.models import model_zoo
    from repro.serving.continuous import ContinuousBatchingEngine
    from repro.serving.router import ServeRouter
    from repro.serving.scheduler import Request

    cfg, mix, seed, seconds, fam = ctx.config, ctx.mix, ctx.seed, ctx.seconds, ctx.family
    dev = jax.devices()[0]
    rec = Record(kind="serve", cfg=cfg, mix=mix, family=fam)
    mcfg = fam.model_config(cfg)
    model = model_zoo.build_model(mcfg)
    with host_span("bench.weights"):
        params = weights_mod.program_tree(fam, cfg, model_zoo.param_specs(model), seed)
        jax.block_until_ready(params)
    max_len = mix["prompt"]["max"] + mix["output"]["max"]
    probe = ContinuousBatchingEngine(mcfg, params, num_slots=1, page_size=PAGE,
                                     max_len=max_len, num_pages=16, device=dev)
    rec.sizing = size_pool(cfg, mix, dev, model, probe, max_len)
    del probe
    rec.sizing.update(max_len=max_len, page_size=PAGE)
    print(f"sizing {rec.sizing}", file=sys.stderr, flush=True)

    steps = rec.steps  # one dict per router step
    cur: dict = {}

    def on_stage(stage, info):
        if stage == "prefill":
            cur["prefills"].append(
                (info["rid"], info["plen"], info["dur_s"], info.get("queue_wait_s"))
            )
        else:
            lens = engine.alloc.seq_lens
            cur["decode"] = (info["dur_s"], info["slots"], lens[lens > 0].copy())

    engine = ContinuousBatchingEngine(
        mcfg, params,
        num_slots=rec.sizing["slots"], page_size=PAGE, max_len=max_len,
        num_pages=rec.sizing["num_pages"], seed=seed, on_stage=on_stage,
        device=dev,
    )
    router = ServeRouter([engine])

    # warm-up: one request per prefill bucket, two tokens each, twice (the
    # first pass meets the freshly made pool, the second the steady state)
    vocab = cfg["vocab_size"]
    rng = np.random.default_rng(seed)
    buckets = warm_buckets(mix, max_len)
    for rnd in range(2):
        for i, b in enumerate(buckets):
            plen = min(b, mix["prompt"]["max"])
            router.submit(Request(
                rid=WARM_RID + rnd * len(buckets) + i, max_new_tokens=2,
                tokens=rng.integers(0, vocab, size=plen, dtype=np.int32)))
        while router.has_work():
            cur.update(prefills=[], decode=None)
            router.step()
    jax.block_until_ready(engine.pages)

    reqs = traffic_gen.serve_requests(mix, seed, seconds, vocab)
    rec.prompts = {r["rid"]: r["prompt"] for r in reqs}
    rec.due = {r["rid"]: r["arrival"] for r in reqs}
    for r in reqs:
        router.submit(Request(rid=r["rid"], tokens=r["prompt"],
                              max_new_tokens=r["max_new"], arrival_time=r["arrival"]))
    outs: dict = {}
    step_return: dict = {}
    open_loop = mix["arrival"] != "backlog"
    if not open_loop:
        # a batch job's steady state: the slots are filled in set-up (one
        # serial prefill per slot), the window measures what follows
        cur.update(prefills=[], decode=None)
        router.step(0.0)
        step_return[0.0] = 0.0
        rec.first.update((p[0], 0.0) for p in cur["prefills"])
    jax.block_until_ready(engine.pages)

    def step(t0: float, into: list) -> float:
        """One router step at the harness's clock; returns the clock after."""
        t = time.perf_counter() - t0
        nxt = engine.next_arrival()
        if nxt is not None and nxt > t:
            with host_span("bench.wait_arrival"):
                time.sleep(nxt - t)
            return time.perf_counter() - t0
        cur.update(prefills=[], decode=None)
        with host_span("bench.router_step"):
            done = router.step(t)
        t_ret = time.perf_counter() - t0
        step_return[t] = t_ret
        into.append(dict(now=t, ret=t_ret, **cur))
        for p in cur["prefills"]:
            rec.first.setdefault(p[0], t_ret)
        for o in done:
            outs[o.rid] = o
        return t_ret

    compiles = Compiles()
    rec.setup_s = time.perf_counter() - ctx.t_start
    ctx.start_trace()
    with compiles, host_span("bench.window"):
        t0 = time.perf_counter()
        t = 0.0
        while t < seconds and router.has_work():
            t = step(t0, steps)
        rec.window_s = t
    ctx.stop_trace()
    rec.compiles = compiles.count
    rec.extra["compiled_in_window"] = sorted(set(compiles.names))
    rec.backlog_left = engine.queue_depth()
    rec.attempted = len(rec.due) if open_loop else len(rec.first)

    # drain: every request due in the window gets its first token, and
    # enough requests finish for the check, or ``drain_s`` runs out
    def unmet() -> bool:
        served = sum(len(o.tokens) for o in outs.values())
        late = open_loop and any(rid not in rec.first for rid in rec.due)
        return late or served < ctx.check_tokens

    drain = []
    while router.has_work() and unmet() and t < rec.window_s + mix["drain_s"]:
        t = step(t0, drain)
    rec.end_s = t
    rec.extra["drain_steps"] = len(drain)
    rec.memory_peak_bytes = peak_bytes(dev)

    rec.token_times = {
        rid: returned_times(o.token_times, step_return) for rid, o in outs.items()
    }
    # requests still decoding: their records travel with the continuations
    for cont in router.drain_continuations():
        carry = getattr(cont, "_carry", None)
        if carry is not None and cont.rid in rec.due:
            rec.token_times[cont.rid] = returned_times(carry.token_times, step_return)
    rec.served = {rid: list(o.tokens) for rid, o in outs.items()}
    rec.failed = sum(1 for rid in rec.due if rid not in rec.first) if open_loop else 0

    del router, engine, params
    gc.collect()
    stats = dev.memory_stats() or {}
    print(f"bytes in use after the program's state is freed: "
          f"{stats.get('bytes_in_use')}", file=sys.stderr)
    return rec


def gaps(ctx, rec: Record, quant=None) -> tuple[float, float, int]:
    """Over a seeded sample of the finished requests, the longest among
    them: the widest gap by which a served token's reference logit lies
    below the reference's best, the same for the token a ``quant`` control
    puts first at each of those positions, and the tokens compared."""
    import jax
    import jax.numpy as jnp

    import reference

    cfg, fam = ctx.config, ctx.family
    w = weights_mod.make(fam, cfg, ctx.seed)
    S = rec.sizing["max_len"]
    n = rec.mix["output"]["max"]
    cfg_json = json.dumps(cfg, sort_keys=True)
    worst = worst_ctrl = 0.0
    count = 0
    for rid in sample_requests(rec, ctx.seed, ctx.check_tokens):
        prompt, served = rec.prompts[rid], rec.served[rid]
        seq = np.zeros((S,), np.int32)
        full = np.concatenate([prompt, np.asarray(served, np.int32)])
        seq[: len(full)] = full
        pos = np.full((n,), len(prompt) - 1, np.int32)
        pos[: len(served)] = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        gap, ctrl = jax.device_get(reference.served_gaps(
            fam, cfg_json, w, jnp.asarray(seq), jnp.asarray(pos), quant))
        worst = max(worst, float(gap.max()))
        worst_ctrl = max(worst_ctrl, float(ctrl.max()))
        count += len(served)
    return worst, worst_ctrl, count


def check(ctx, rec: Record) -> dict:
    """The window compiled nothing, a backlog was not drained, and the
    served tokens agree with the reference (``gaps``)."""
    checks = {"window_compiles": {"value": rec.compiles, "max": 0}}
    if rec.mix["arrival"] == "backlog":
        checks["backlog_left"] = {"value": rec.backlog_left, "min": 1}
    checks["finished_requests"] = {"value": len(rec.served), "min": 1}
    if rec.served:
        worst, _, count = gaps(ctx, rec)
        checks["served_logit_gap"] = {"value": worst,
                                      "max": ctx.limits["served_logit_gap"]}
        checks["served_tokens_compared"] = {"value": count, "min": 1}
    return checks


def sample_requests(rec: Record, seed: int, tokens: int) -> list[int]:
    """The longest finished request, then others in a seeded order until
    ``tokens`` served tokens are covered."""
    done = sorted(rec.served, key=lambda r: (-len(rec.served[r]), r))
    pick, total = [done[0]], len(rec.served[done[0]])
    rest = done[1:]
    for i in np.random.default_rng(seed).permutation(len(rest)):
        if total >= tokens:
            break
        pick.append(rest[i])
        total += len(rec.served[rest[i]])
    return pick
