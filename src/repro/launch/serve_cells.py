"""Pool-level serve-cell tier CLI — one serve *job* per cell (§4.3).

    PYTHONPATH=src python -m repro.launch.serve_cells --arch qwen2-0.5b \
        --requests 16 --cells auto --replicas 1 --max-replicas 2

The cross-job layer of the serving stack: the pool's free shape is planned
into cells (:func:`repro.launch.cells.serve_cell_plan`), the workload's
requests are join-shortest-queue assigned across the cells by a
:class:`~repro.serving.cell_router.CellRouter` (the same deterministic
tie-break the in-job tier uses), and each cell is submitted as its own
``serve`` job on the shared platform pool — so cells are scheduled,
preempted, resumed and retried independently.  ``--max-replicas`` above
``--replicas`` turns on sustained-queue-depth replica autoscaling inside
each cell, and a cell job that fails terminally (container retries
exhausted) has its requests salvaged: rerouted across the surviving cells
and served by follow-up jobs.
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from repro.compile_cache import enable_compilation_cache
from repro.launch.cells import serve_cell_plan
from repro.platform import DONE, JobSpec, Platform, ServeJobConfig
from repro.serving.cell_router import CellRouter
from repro.serving.scheduler import Request


class _PlannedCell:
    """Client-side stand-in during assignment: accumulates the token load
    routed to the cell job under construction (JSQ balances on it)."""

    def __init__(self, devices: int):
        self.devices = devices
        self.assigned: list[Request] = []
        self._tokens = 0
        self.replicas = 1

    def submit(self, req: Request) -> None:
        self.assigned.append(req)
        self._tokens += req.prompt_len + req.max_new_tokens

    def load_tokens(self) -> int:
        return self._tokens

    def queue_depth(self) -> int:
        return len(self.assigned)

    def has_work(self) -> bool:
        return False  # assignment only; the serve jobs do the work

    def drain_continuations(self) -> list[Request]:
        drained, self.assigned = self.assigned, []
        self._tokens = 0
        return drained

    def scale_to(self, n: int) -> int:
        self.replicas = max(1, n)
        return self.replicas


def _assign(router: CellRouter, reqs: list[Request]) -> None:
    for r in reqs:
        router.submit(r)


def _cell_spec(args, ci: int, devices: int, batch: int, suffix: str = "") -> JobSpec:
    return JobSpec(
        kind="serve",
        name=f"cell{ci}{suffix}",
        config=ServeJobConfig(
            arch=args.arch, scale=args.scale, batch=batch,
            prompt_len=args.prompt_len, gen=args.gen, seed=args.seed + ci,
            engine="continuous", page_size=args.page_size, slots=args.slots,
            replicas=args.replicas, max_replicas=args.max_replicas,
            deadline_s=args.deadline_s,
            # predictive scaling only makes sense with autoscale headroom
            predictive_autoscale=(
                args.predictive_autoscale
                and args.max_replicas > args.replicas
            ),
            spec_k=args.spec_k, prefix_cache=args.prefix_cache,
            prefill_chunk=args.prefill_chunk,
        ),
        devices=devices,
        priority=args.priority,
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--scale", choices=["smoke", "full"], default="smoke")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots per engine replica")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas each cell starts with")
    ap.add_argument("--max-replicas", type=int, default=0,
                    help="autoscale ceiling per cell (0 disables)")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="per-request latency budget each cell job's "
                         "deadline admission enforces (0 disables)")
    ap.add_argument("--predictive-autoscale", action="store_true",
                    help="cells scale replicas on forecast arrival rate "
                         "(needs --max-replicas above --replicas)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding inside each cell's engines "
                         "(0 disables)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share prompt-prefix K/V pages inside each cell")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="fused chunked prefill budget per step per slot "
                         "(0 keeps bucketed prefill)")
    ap.add_argument("--cells", default="auto",
                    help="cell count, or 'auto' to derive from free runs")
    ap.add_argument("--devices-per-cell", type=int, default=2)
    ap.add_argument("--pool-devices", type=int, default=8)
    ap.add_argument("--priority", type=int, default=0)
    ap.add_argument("--salvage-retries", type=int, default=2,
                    help="salvage rounds for failed cell jobs before giving up")
    ap.add_argument("--salvage-backoff-s", type=float, default=0.5,
                    help="base delay between salvage rounds (doubles per round)")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    platform = Platform(total_devices=args.pool_devices)
    plan = serve_cell_plan(
        platform.rm,
        cells=0 if args.cells == "auto" else int(args.cells),
        devices_per_cell=args.devices_per_cell,
    )
    print(f"[serve_cells] plan: {len(plan)} cells x {plan[0]} devices "
          f"(pool={args.pool_devices})")

    # JSQ-assign the workload across the planned cells (deterministic)
    planned = [_PlannedCell(d) for d in plan]
    router = CellRouter(planned)
    _assign(router, [
        Request(rid=i, tokens=np.zeros((args.prompt_len,), np.int32),
                max_new_tokens=args.gen)
        for i in range(args.requests)
    ])
    print(f"[serve_cells] assignment: {router.routed} requests/cell")

    # one serve job per non-empty cell, scheduled independently on the pool
    specs, spec_cells = [], []
    for ci, cell in enumerate(planned):
        if not cell.assigned:
            continue
        specs.append(_cell_spec(args, ci, cell.devices, len(cell.assigned)))
        spec_cells.append(ci)
    # the cell map keys by the *returned* uniquified names: on a shared
    # platform a same-named tenant shifts ours to "-2" suffixes, and the
    # request-side spec.name would no longer match the report keys
    names = platform.submit_batch(specs)
    cell_of = dict(zip(names, spec_cells))
    reports = platform.wait(names)
    assert isinstance(reports, dict)

    # whole-cell salvage with a retry cap + exponential backoff: a cell job
    # that failed terminally has its requests rerouted across the surviving
    # cells and served by follow-up jobs; follow-ups that fail too are
    # salvaged again, up to --salvage-retries rounds
    failed = {n: r for n, r in reports.items() if r.state != DONE}
    round_no = 0
    while failed and round_no < args.salvage_retries:
        round_no += 1
        survivors = [
            ci for ci, cell in enumerate(planned)
            if router.alive[ci] and not any(cell_of[n] == ci for n in failed)
        ]
        if not survivors:
            print("[serve_cells] every cell failed; nothing to salvage")
            sys.exit(1)
        salvaged = []
        for n, rep in failed.items():
            ci = cell_of[n]
            router.alive[ci] = False
            lost = planned[ci].drain_continuations()
            print(f"[serve_cells] cell {ci} failed ({rep.error}); "
                  f"salvaging {len(lost)} requests across cells {survivors}")
            salvaged.extend(lost)
        if not salvaged:
            break
        delay = args.salvage_backoff_s * (2 ** (round_no - 1))
        if delay > 0:
            print(f"[serve_cells] salvage round {round_no}/"
                  f"{args.salvage_retries}: backing off {delay:.2f}s "
                  "before resubmitting")
            time.sleep(delay)
        # survivors' earlier requests were already served by their original
        # jobs; clear them so a failed *salvage* job only re-salvages its own
        for si in survivors:
            planned[si].drain_continuations()
        before = list(router.routed)
        _assign(router, salvaged)  # JSQ across the surviving cells
        router.salvaged += len(salvaged)
        salvage_specs, salvage_cells = [], []
        for si in survivors:
            extra = router.routed[si] - before[si]
            if extra > 0:
                salvage_specs.append(_cell_spec(
                    args, si, plan[si], extra, suffix=f"-salvage{round_no}"))
                salvage_cells.append(si)
        if salvage_specs:
            salvage_names = platform.submit_batch(salvage_specs)
            cell_of.update(zip(salvage_names, salvage_cells))
            fresh = platform.wait(salvage_names)
            assert isinstance(fresh, dict)
        else:
            fresh = {}
        reports.update(fresh)
        failed = {n: r for n, r in fresh.items() if r.state != DONE}
    if failed:
        print(f"[serve_cells] salvage budget exhausted after {round_no} "
              f"round(s); still failed: {sorted(failed)}")

    print("\n=== serve-cell tier ===")
    total_tokens, total_wall = 0, 0.0
    for name, rep in sorted(reports.items()):
        print(rep.summary())
        total_tokens += rep.metrics.get("tokens", 0)
        total_wall = max(total_wall, rep.wall_time_s)
    waits = [r.queue_time_s for r in reports.values()]
    print(
        f"[serve_cells] {len(reports)} cell jobs, {total_tokens} tokens, "
        f"p50 cell queue wait {np.percentile(waits, 50):.3f}s, "
        f"tier stats {router.stats()}"
    )
    if any(r.state != DONE for r in reports.values()):
        sys.exit(1)


if __name__ == "__main__":
    main()
