"""Host-side batch loader over an RDD of token records.

Background prefetch thread + straggler mitigation: every partition fetch is
raced against a timeout; slow fetches trigger a speculative duplicate fetch
(Spark's backup-task trick applied at the data-pipeline level, the one place
stragglers can exist inside an SPMD step — DESIGN.md §6)."""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, Optional

import numpy as np

from repro.core.rdd import ShardedDataset
from repro.obs import annotate


class BatchLoader:
    def __init__(
        self,
        dataset: ShardedDataset,
        batch_size: int,
        prefetch: int = 2,
        straggler_timeout_s: Optional[float] = None,
    ):
        self.dataset = dataset
        self.batch_size = batch_size
        self.prefetch = prefetch
        self.straggler_timeout_s = straggler_timeout_s
        self.speculative_fetches = 0
        self._queue: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    def _fetch_partition(self, idx: int) -> list[dict]:
        if self.straggler_timeout_s is None:
            return self.dataset.compute_partition(idx)
        result: list = []
        done = threading.Event()

        def work():
            try:
                result.append(self.dataset.compute_partition(idx))
            finally:
                done.set()

        t = threading.Thread(target=work, daemon=True)
        t.start()
        if not done.wait(self.straggler_timeout_s):
            # primary is a straggler: launch a backup (lineage is deterministic,
            # either copy is valid); take whichever finishes first
            self.speculative_fetches += 1
            backup_done = threading.Event()

            def backup():
                try:
                    result.append(self.dataset.compute_partition(idx))
                finally:
                    backup_done.set()

            tb = threading.Thread(target=backup, daemon=True)
            tb.start()
            while not result:
                time.sleep(0.001)
        while not result:
            time.sleep(0.001)
        return result[0]

    def _producer(self, epochs: int):
        buf: list[dict] = []
        for _ in range(epochs):
            for p in range(self.dataset.num_partitions):
                if self._stop.is_set():
                    return
                buf.extend(self._fetch_partition(p))
                while len(buf) >= self.batch_size:
                    recs, buf = buf[: self.batch_size], buf[self.batch_size :]
                    batch = {
                        "tokens": np.stack([r["tokens"] for r in recs]),
                        "targets": np.stack([r["targets"] for r in recs]),
                    }
                    self._queue.put(batch)
        self._queue.put(None)

    # ------------------------------------------------------------------
    def batches(self, epochs: int = 1) -> Iterator[dict]:
        self._thread = threading.Thread(target=self._producer, args=(epochs,), daemon=True)
        self._thread.start()
        while True:
            with annotate("repro.loader.wait"):
                item = self._queue.get()
            if item is None:
                return
            yield item

    def close(self):
        self._stop.set()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
