"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV on stdout (harness contract). The
staging/labeling hot-path microbenchmark is produced by ``--staging``, the
batch-vs-streaming turnaround comparison by ``--streaming``, and the
multi-tenant staging-service scenario by ``--service``, the
fault-tolerance repair-vs-restage comparison by ``--faults``, the
QoS-vs-FIFO concurrent-session scheduling sweep by ``--qos``, and the
cross-facility WAN ingest fan-out/jitter sweep by ``--wan`` (each also
emits its ``BENCH_*.json``; standalone: ``python -m benchmarks.bench_<name>``).
``--wan --quick`` asserts the zero-jitter/zero-loss WAN path byte- and
time-exact vs the local streaming engine plus the pub/sub fan-out
invariant — the CI WAN-parity smoke.
``--staging --quick`` skips every wall-clock comparison and instead
asserts the SIMULATED FLAT-topology accounting (plus the topology-plan
costs) match the recorded ``BENCH_staging.json`` baseline exactly — the
CI accounting-parity smoke. ``--faults --quick`` does the same for the
fault model against ``BENCH_faults.json`` (including the zero-fault
bit-exactness anchor against the staging baseline), and ``--qos --quick``
for the scheduler against the small deterministic anchor recorded in
``BENCH_qos.json``.

``--staging --quick --trace`` additionally records a full telemetry
timeline during the parity runs (`repro.core.telemetry`) and exports the
largest-P Chrome trace to ``benchmarks/TRACE_staging.json`` (load it at
https://ui.perfetto.dev) — parity holding tracer-ON is the CI
telemetry-neutrality smoke.

Every invocation ends with a consolidated summary of ALL ``BENCH_*.json``
files present (on stderr, so the stdout CSV contract is preserved),
including the fabric calibration each was measured under, which staging
API surface drove it (``legacy shim`` vs ``client``), and — for result
files carrying a telemetry ``metrics`` block — a P50/P99 column from the
shared registry histograms (QoS latency, stage totals, per-collective
durations; see docs/observability.md).
"""
from __future__ import annotations

import glob
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the repo root, so `python benchmarks/run.py` resolves the benchmarks
# package exactly like `python -m benchmarks.run`
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _headline(name: str, report: dict) -> str:
    """One-line takeaway per known BENCH_*.json schema (generic fallback)."""
    try:
        if name == "BENCH_staging.json":
            s = report["staging"][-1]          # largest host count
            lab = report["labeling"]
            head = (f"{s['name']} {s['speedup']:.1f}x vs legacy; "
                    f"labeling {lab['speedup']:.0f}x")
            hp = report.get("hook_paths")
            if hp:
                head += (f"; shim==client accounting: "
                         f"{hp['simulated_accounting_match']}")
            topo = report.get("topology")
            if topo:
                t = topo[-1]                   # largest host count
                head += (f"; {t['name']} hier "
                         f"{t['speedup_hier_vs_flat']:.1f}x vs flat ring")
            return head
        if name == "BENCH_streaming.json":
            rs = report["turnaround"]
            lo = min(r["speedup"] for r in rs)
            hi = max(r["speedup"] for r in rs)
            return (f"stream vs batch {lo:.2f}-{hi:.2f}x over "
                    f"{len(rs)} rates, byte-exact")
        if name == "BENCH_faults.json":
            rr = report["repair_vs_restage"][-1]     # largest host count
            a = report["zero_fault_anchor"]
            return (f"repair {rr['speedup']:.0f}x vs re-stage "
                    f"@P{rr['name'].rsplit('P', 1)[1]}; zero-fault "
                    f"bit-exact: {a['bit_exact']}")
        if name == "BENCH_service.json":
            svc, wb = report["service"], report["writeback"]
            return (f"{svc['stages']} stages/{svc['coalesced']} coalesced/"
                    f"{svc['evictions']} evictions; stage_out "
                    f"{wb['speedup']:.1f}x vs naive @P{wb['n_hosts']}")
        if name == "BENCH_wan.json":
            fan = report["fanout"][-1]               # largest subscriber count
            sweep = report["jitter_sweep"]
            dropped = sum(r["frames_dropped"] for r in sweep)
            return (f"pub/sub {fan['wan_bytes_ratio']:.0f}x fewer WAN bytes "
                    f"@N={fan['subscribers']}; anchor byte-exact: "
                    f"{report['anchor']['byte_exact']}; jitter sweep "
                    f"{len(sweep)} seeds replay-exact ({dropped} drops "
                    f"accounted)")
        if name == "BENCH_compression.json":
            w = report["wan_headline"]
            hi = max(r["speedup"] for r in report["hierarchical"])
            crossed = sum(1 for r in report["crossover"] if r["compressed"])
            return (f"WAN wire {w['wan_bytes_ratio']:.1f}x smaller "
                    f"(frame-lossless); hierarchical up to {hi:.1f}x; "
                    f"crossover {crossed}/{len(report['crossover'])} cells "
                    f"compressed; identity anchor exact: "
                    f"{report['anchor']['byte_exact']}")
        if name == "BENCH_qos.json":
            by = {(r["rate_hz"], r["policy"]): r for r in report["open_loop"]}
            rate = max(r for r, _ in by)
            f, q = by[(rate, "fifo")], by[(rate, "qos")]
            return (f"qos P99 {f['p99_latency'] / q['p99_latency']:.1f}x "
                    f"better than fifo @{rate:g}req/s "
                    f"(P{report['config']['n_hosts']}), goodput "
                    f"{f['goodput_bytes_per_s'] / 1e6:.0f}->"
                    f"{q['goodput_bytes_per_s'] / 1e6:.0f}MB/s")
    except Exception:
        pass          # a malformed result file must never kill the summary
    try:
        return ", ".join(sorted(report)[:4])
    except Exception:
        return "-"


def _calibration(report: dict) -> str:
    try:
        return (report.get("calibration")
                or report.get("config", {}).get("calibration", "-"))
    except Exception:
        return "-"


def _api_path(report: dict) -> str:
    """Which staging API surface the bench drove: the unified client, the
    legacy run_io_hook shim, or '-' for pre-redesign result files."""
    try:
        return (report.get("api_path")
                or report.get("config", {}).get("api_path", "-"))
    except Exception:
        return "-"


# which registry histogram a result file's P50/P99 column quotes, in
# preference order (the first one present with observations wins)
_SUMMARY_HISTOGRAMS = ("qos.latency_s", "stage.total_s",
                       "stream.frame_latency_s", "collective.duration_s")


def _percentiles(report: dict) -> str:
    """``hist=P50/P99`` from the report's telemetry ``metrics`` block
    (the shared `repro.core.telemetry.MetricsRegistry` snapshot), or
    '-' for result files recorded before the telemetry PR."""
    try:
        hists = report.get("metrics", {}).get("histograms", {})
        for name in _SUMMARY_HISTOGRAMS:
            h = hists.get(name)
            if h and h.get("count") and h.get("p50") is not None:
                return f"{name}={h['p50']:.3f}/{h['p99']:.3f}s"
    except Exception:
        pass
    return "-"


def print_summary(out=sys.stderr) -> None:
    """Consolidated table across every BENCH_*.json in this directory."""
    paths = sorted(glob.glob(os.path.join(BENCH_DIR, "BENCH_*.json")))
    if not paths:
        return
    rows = []
    for path in paths:
        try:
            with open(path) as f:
                report = json.load(f)
        except (OSError, json.JSONDecodeError):
            rows.append((os.path.basename(path), "-", "-", "-",
                         "unreadable"))
            continue
        rows.append((os.path.basename(path), _calibration(report),
                     _api_path(report), _percentiles(report),
                     _headline(os.path.basename(path), report)))
    w_name = max(len(r[0]) for r in rows)
    w_cal = max(max(len(r[1]) for r in rows), len("calibration"))
    w_api = max(max(len(r[2]) for r in rows), len("api_path"))
    w_pct = max(max(len(r[3]) for r in rows), len("p50/p99"))
    print(f"\n== BENCH summary ({len(rows)} result files) ==", file=out)
    print(f"{'file':<{w_name}}  {'calibration':<{w_cal}}  "
          f"{'api_path':<{w_api}}  {'p50/p99':<{w_pct}}  headline", file=out)
    for name, cal, api, pct, head in rows:
        print(f"{name:<{w_name}}  {cal:<{w_cal}}  {api:<{w_api}}  "
              f"{pct:<{w_pct}}  {head}", file=out)


def main() -> None:
    print("name,us_per_call,derived")
    try:
        if "--staging" in sys.argv[1:]:
            from benchmarks import bench_staging
            quick = "--quick" in sys.argv[1:]
            trace = "--trace" in sys.argv[1:]
            print(f"[bench_staging] api_path={bench_staging.API_PATH}"
                  f"{' quick=sim-parity-only' if quick else ''}"
                  f"{' trace=on' if trace else ''}",
                  file=sys.stderr)
            for name, us, derived in bench_staging.rows(quick=quick,
                                                        trace=trace):
                print(f"{name},{us:.1f},{derived}")
            if trace and quick:
                print(f"[bench_staging] wrote {bench_staging.TRACE_PATH} "
                      f"(load at https://ui.perfetto.dev)", file=sys.stderr)
        elif "--streaming" in sys.argv[1:]:
            from benchmarks import bench_streaming
            print(f"[bench_streaming] api_path={bench_streaming.API_PATH}",
                  file=sys.stderr)
            for name, us, derived in bench_streaming.rows():
                print(f"{name},{us:.1f},{derived}")
        elif "--service" in sys.argv[1:]:
            from benchmarks import bench_service
            print(f"[bench_service] api_path={bench_service.API_PATH}",
                  file=sys.stderr)
            for name, us, derived in bench_service.rows():
                print(f"{name},{us:.1f},{derived}")
        elif "--faults" in sys.argv[1:]:
            from benchmarks import bench_faults
            quick = "--quick" in sys.argv[1:]
            print(f"[bench_faults] api_path={bench_faults.API_PATH}"
                  f"{' quick=sim-parity-only' if quick else ''}",
                  file=sys.stderr)
            for name, us, derived in bench_faults.rows(quick=quick):
                print(f"{name},{us:.1f},{derived}")
        elif "--qos" in sys.argv[1:]:
            from benchmarks import bench_qos
            quick = "--quick" in sys.argv[1:]
            print(f"[bench_qos] api_path={bench_qos.API_PATH}"
                  f"{' quick=sim-parity-only' if quick else ''}",
                  file=sys.stderr)
            for name, us, derived in bench_qos.rows(quick=quick):
                print(f"{name},{us:.1f},{derived}")
        elif "--wan" in sys.argv[1:]:
            from benchmarks import bench_wan
            quick = "--quick" in sys.argv[1:]
            print(f"[bench_wan] api_path={bench_wan.API_PATH}"
                  f"{' quick=anchor-parity-only' if quick else ''}",
                  file=sys.stderr)
            for name, us, derived in bench_wan.rows(quick=quick):
                print(f"{name},{us:.1f},{derived}")
        elif "--compression" in sys.argv[1:]:
            from benchmarks import bench_compression
            quick = "--quick" in sys.argv[1:]
            print(f"[bench_compression] "
                  f"api_path={bench_compression.API_PATH}"
                  f"{' quick=anchor-parity-only' if quick else ''}",
                  file=sys.stderr)
            for name, us, derived in bench_compression.rows(quick=quick):
                print(f"{name},{us:.1f},{derived}")
        else:
            from benchmarks import paper_figures
            for fn in paper_figures.ALL:
                for name, us, derived in fn():
                    print(f"{name},{us:.1f},{derived}")
    finally:
        print_summary()


if __name__ == "__main__":
    main()
