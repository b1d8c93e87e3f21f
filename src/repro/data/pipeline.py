"""Staged training-data pipeline: the paper's technique as the input path.

A dataset lives on the shared FS as shard files. Per training wave:
  * leaders resolve the shard manifest ONCE (iohook) and collectively stage
    each host's assigned shards into node-local stores (aggregate FS read =
    1x dataset, paper §IV),
  * hosts cut batches from node-local data at RAM speed; repeats (multiple
    epochs / eval reuse) hit the pinned cache at zero FS cost (§VI-B).

`StagedLoader.batches()` yields jnp batches for a training step; the simulated-
time accounting (stage vs naive) feeds the benchmarks.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

import jax.numpy as jnp

from repro.core.api import (BroadcastEntry, CollectiveConfig, NaiveConfig,
                            StagingClient, StagingSpec)
from repro.core.fabric import Fabric
from repro.core.staging import StagingReport


def write_token_shards(fabric: Fabric, n_shards: int, tokens_per_shard: int,
                       vocab: int, seed: int = 0, prefix: str = "data"
                       ) -> List[str]:
    """Synthesize a token dataset as shard files on the shared FS."""
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(n_shards):
        toks = rng.integers(0, vocab, tokens_per_shard, dtype=np.int32)
        path = f"{prefix}/shard_{i:04d}.bin"
        fabric.fs.put(path, toks.view(np.uint8))
        paths.append(path)
    return paths


@dataclass
class StagedLoader:
    fabric: Fabric
    pattern: str
    batch: int
    seq: int
    host_id: int = 0
    staging_time: float = 0.0
    _data: Optional[np.ndarray] = None

    def stage(self, collective: bool = True, config=None) -> StagingReport:
        """Stage the shard manifest through the unified client; returns the
        staging report (simulated time). `config` is an optional typed
        engine config (`repro.core.api`); the legacy ``collective``
        boolean maps to Collective/NaiveConfig when `config` is None."""
        if config is None:
            config = CollectiveConfig() if collective else NaiveConfig()
        spec = StagingSpec([BroadcastEntry(files=(self.pattern,), pin=True)])
        res = StagingClient(self.fabric).stage(spec, config)
        self.staging_time = res.total_time
        store = self.fabric.hosts[self.host_id].store
        blobs = [store.data[p] for p in sorted(res.resolved_files)]
        self._data = np.concatenate(blobs).view(np.int32)
        return res.reports[0]

    def batches(self, seed: int = 0) -> Iterator[Dict[str, jnp.ndarray]]:
        """Yield {tokens, labels} batches from node-local data."""
        if self._data is None:
            raise RuntimeError("call stage() first")
        rng = np.random.default_rng(seed)
        n_tok = self.batch * self.seq
        while True:
            start = int(rng.integers(0, max(1, len(self._data) - n_tok - 1)))
            window = self._data[start:start + n_tok].reshape(self.batch,
                                                             self.seq)
            toks = jnp.asarray(window)
            yield {"tokens": toks, "labels": toks}
