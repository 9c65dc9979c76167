"""Throwaway driver kinds for the isolation/chaos suites.

Importing this module registers the drivers.  It is imported both by the
test process and — via the ``REPRO_ISOLATION_IMPORT`` hook — inside
isolated child workers (pytest puts ``tests/`` on ``sys.path`` and the
isolation supervisor ships the parent's ``sys.path`` through
``PYTHONPATH``, so the child resolves it the same way).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from repro.platform.driver import ContainerFailure, register_driver


@dataclasses.dataclass
class SleeperConfig:
    naps: int = 5
    nap_s: float = 0.02
    report_devices: bool = False  # metrics["devices"] = jax.device_count()
    stuck: bool = False  # sleep forever without ever checkpointing
    ignore_sigterm: bool = False  # force the ladder all the way to SIGKILL
    ready_file: str = ""  # touched once running, after any SIGTERM handler


@register_driver
class SleeperDriver:
    """Naps between checkpoints.  ``stuck`` makes it hold its devices
    without ever reaching another cancellation point — the workload class
    cooperative interruption cannot stop and enforcement exists for."""

    kind = "sleeper"

    def prepare(self, spec) -> SleeperConfig:
        cfg = spec.config
        if isinstance(cfg, SleeperConfig):
            return cfg
        return SleeperConfig(**(cfg or {}))

    def run(self, container, cfg: SleeperConfig, token=None) -> dict:
        if cfg.ignore_sigterm:
            import signal

            signal.signal(signal.SIGTERM, signal.SIG_IGN)
        if cfg.ready_file:
            open(cfg.ready_file, "w").close()
        devices: Optional[int] = None
        if cfg.report_devices:
            import jax

            devices = jax.device_count()
        if cfg.stuck:
            # no cancellation point, ever: cooperative interruption cannot
            # touch this worker, only enforcement can
            while True:
                time.sleep(0.05)
        for _ in range(cfg.naps):
            if token is not None:
                token.checkpoint()
            time.sleep(cfg.nap_s)
        return {"naps": cfg.naps, "devices": devices}


@dataclasses.dataclass
class FlakyConfig:
    fail_attempts: int = 2  # raise ContainerFailure on the first N attempts
    dead_devices: int = 0  # 0: worker lost, devices fine
    units: int = 3


@register_driver
class FlakyDriver:
    """Raises ContainerFailure on its first ``fail_attempts`` attempts, then
    succeeds — the retry/backoff path's deterministic workload."""

    kind = "crashy"

    def prepare(self, spec) -> FlakyConfig:
        cfg = spec.config
        if isinstance(cfg, FlakyConfig):
            return cfg
        return FlakyConfig(**(cfg or {}))

    def run(self, container, cfg: FlakyConfig, token=None) -> dict:
        state = token.state if token is not None else {}
        attempt = state.get("attempt", 0) + 1
        state["attempt"] = attempt
        if attempt <= cfg.fail_attempts:
            raise ContainerFailure(
                f"flaky attempt {attempt} died", dead_devices=cfg.dead_devices
            )
        for _ in range(cfg.units):
            if token is not None:
                token.checkpoint()
        return {"attempt": attempt}
