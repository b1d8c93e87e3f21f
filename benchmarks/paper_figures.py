"""Benchmarks mirroring the paper's tables/figures.

Each function returns a list of (name, us_per_call, derived) rows;
benchmarks/run.py prints them as CSV. Simulated-fabric times use the
BGQ-calibrated constants (repro.core.fabric — fit to the paper's measured
aggregates). Chip timings come from benchmarks/chip, not from here.
"""
from __future__ import annotations

import random
from typing import List, Tuple

import numpy as np

Row = Tuple[str, float, str]


def _mk_fabric(n_hosts, n_files=736, per_file=577 * 2**20 // 736):
    from repro.core.fabric import BGQ, Fabric
    fab = Fabric(n_hosts=n_hosts, constants=BGQ)
    blob = np.zeros(per_file, np.uint8)
    paths = []
    for i in range(n_files):
        fab.fs.files[f"d/{i}.bin"] = blob      # shared buffer (RAM frugal)
        paths.append(f"d/{i}.bin")
    return fab, paths


def fig10_staging_write() -> List[Row]:
    """Staging+Write aggregate bandwidth vs node count (Fig. 10)."""
    from repro.core.staging import stage_collective
    rows = []
    for hosts in (256, 512, 1024, 2048, 4096, 8192):
        fab, paths = _mk_fabric(hosts)
        rep, _ = stage_collective(fab, paths)
        rows.append((f"fig10_staging_write_n{hosts}",
                     rep.total_time * 1e6,
                     f"agg_GBps={rep.delivered_bandwidth/1e9:.1f}"))
    return rows


def fig11_end_to_end() -> List[Row]:
    """End-to-end input: hook vs naive at 8192 nodes (Fig. 11 + §VI-B).
    Paper: 46.75 s vs 210 s (4.7x); 101 vs 21 GB/s."""
    from repro.core.fabric import BGQ
    from repro.core.staging import stage_collective
    fab, paths = _mk_fabric(8192)
    rep, _ = stage_collective(fab, paths)
    read_phase = 577 * 2**20 / BGQ.local_read_bw
    hook_total = rep.total_time + read_phase
    naive_total = 8192 * 577 * 2**20 / BGQ.fs_rand_bw
    agg = 8192 * 577 * 2**20
    return [
        ("fig11_hook_end_to_end", hook_total * 1e6,
         f"agg_GBps={agg/hook_total/1e9:.1f}"),
        ("fig11_naive_end_to_end", naive_total * 1e6,
         f"agg_GBps={agg/naive_total/1e9:.1f}"),
        ("fig11_input_time_ratio", 0.0,
         f"ratio={naive_total/hook_total:.2f}x_paper=4.7x"),
    ]


def _makespan_rows(tag, n_tasks, dur_range, workers_list, seed=1) -> List[Row]:
    from repro.core.fabric import Fabric
    from repro.core.manytask import ManyTaskEngine, Task
    r = random.Random(seed)
    durations = [r.uniform(*dur_range) for _ in range(n_tasks)]
    rows = []
    for w in workers_list:
        fab = Fabric(n_hosts=max(1, w // 16), ranks_per_host=16)
        eng = ManyTaskEngine(fab, n_workers=w)
        stats = eng.run([Task(task_id=i, duration=d)
                         for i, d in enumerate(durations)])
        eff = stats.cpu_seconds() / (stats.makespan * w)
        rows.append((f"{tag}_w{w}", stats.makespan * 1e6,
                     f"efficiency={eff*100:.0f}%"))
    return rows


def fig12_ff_stage1_makespan() -> List[Row]:
    """FF-HEDM stage 1: 720 jobs, 5-160 s each (Fig. 12)."""
    return _makespan_rows("fig12_ff1", 720, (5, 160), (40, 80, 160, 320))


def fig13_ff_stage2_makespan() -> List[Row]:
    """FF-HEDM stage 2: 4,109 jobs, 5-25 s each (Fig. 13)."""
    return _makespan_rows("fig13_ff2", 4109, (5, 25), (40, 80, 160, 320))


def metadata_contention() -> List[Row]:
    """§IV: leader-glob + broadcast vs per-rank glob storm."""
    from repro.core.fabric import BGQ, Fabric
    from repro.core.iohook import naive_per_rank_globs, resolve_manifest
    fab = Fabric(n_hosts=512, ranks_per_host=16, constants=BGQ)
    for i in range(64):
        fab.fs.put(f"s/f{i}.py", np.ones(64, np.uint8))
    _, t_leader = resolve_manifest(fab, ["s/*.py"], 0.0)
    fab2 = Fabric(n_hosts=512, ranks_per_host=16, constants=BGQ)
    for i in range(64):
        fab2.fs.put(f"s/f{i}.py", np.ones(64, np.uint8))
    t_naive = naive_per_rank_globs(fab2, ["s/*.py"])
    return [("metadata_leader_glob", t_leader * 1e6, ""),
            ("metadata_per_rank_glob", t_naive * 1e6,
             f"ratio={t_naive/max(t_leader,1e-12):.0f}x")]


def checkpoint_staged_restore() -> List[Row]:
    """Staging applied to checkpoint restore: collective (1x read + ICI
    all-gather) vs naive (P x reads), modeled on the TPU fabric."""
    from repro.core.fabric import TPU_POD
    c = TPU_POD
    ckpt = 16 * 2 ** 30                      # 16 GiB checkpoint
    rows = []
    for hosts in (64, 256):
        t_coll = (c.coll_latency_base + c.coll_latency_log * np.log2(hosts)
                  + ckpt / c.fs_seq_bw
                  + (ckpt / hosts) / c.link_bw * (hosts - 1)
                  + ckpt / c.local_bw)
        t_naive = hosts * ckpt / c.fs_rand_bw + ckpt / c.local_bw
        rows.append((f"ckpt_restore_collective_n{hosts}", t_coll * 1e6,
                     f"GBps={ckpt/t_coll/1e9:.1f}"))
        rows.append((f"ckpt_restore_naive_n{hosts}", t_naive * 1e6,
                     f"GBps={ckpt/t_naive/1e9:.1f}"))
    return rows


ALL = [fig10_staging_write, fig11_end_to_end, fig12_ff_stage1_makespan,
       fig13_ff_stage2_makespan, metadata_contention,
       checkpoint_staged_restore]
