"""Benchmark driver — one entry per paper table/figure (+ roofline, engine).

Prints ``name,us_per_call,derived`` CSV and writes the structured results to
a BENCH JSON file (default ``benchmarks/BENCH.json``, override with
``BENCH_JSON=path``) so CI can upload it as an artifact and entries stay
comparable across PRs (see README "Benchmark methodology").

  * name        — paper artifact the benchmark reproduces
  * us_per_call — wall time of one benchmark unit (microseconds)
  * derived     — the headline metric(s) the paper reports

``BENCH_QUICK=1`` runs a reduced smoke mode (CI): smaller tx counts, same
assertions except the 1M-tx speedup floor (which needs the full run).

``python benchmarks/run.py --all`` runs NO benchmarks: it aggregates every
``BENCH_*.json`` already in ``benchmarks/`` into one summary table (stdout)
and writes ``BENCH_summary.json`` — the cross-PR comparison view CI
artifacts are diffed against.  The summary embeds the ``repro.api``
NodeSpec preset catalog (``_presets``): each bench declares its node
scenario as data there, so a PR that changes a scenario shows up as a
spec diff in the artifact.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _timed(fn, *args, **kw):
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) * 1e6


# headline metric extractors per BENCH file stem (best-effort: files from
# older PRs may miss keys; the aggregator records what it finds)
_HEADLINES = {
    "BENCH_engine": lambda d: {
        "speedup": d["out"]["speedup"], "n_txs": d["out"]["n_txs"]},
    "BENCH_protocol": lambda d: {
        "speedup": d["speedup"],
        "mega_speedup": d["mega_speedup"],
        "fl_per_task_flatness": d["fl_per_task_flatness"],
        "window_loop_speedup": d["window_loop"]["fused_speedup"],
        "window_loop_flatness": d["window_loop"]["per_task_flatness"],
        "assert_point": d["assert_point"]},
    "BENCH_shards": lambda d: {
        "scaling": d["scaling"],
        "wall_scaling": d["wall_scaling"],
        "shard_counts": d["shard_counts"],
        "state_root": d["state_root"]},
    "BENCH_prover": lambda d: {
        "verify_gas_reduction": d["reduction"],
        "widths": d["widths"],
        "backends": sorted(d["backends"])},
    "BENCH_serve": lambda d: {
        "honest_retention": d["honest_retention"],
        "admitted_tps": d["admitted_tps"],
        "n_clients": d["n_clients"]},
    "BENCH": lambda d: {
        "entries": sorted(d["results"])},
}


def aggregate_all(bench_dir: str) -> dict:
    """Fold every BENCH_*.json (and BENCH.json) into one summary dict."""
    summary = {}
    for fname in sorted(os.listdir(bench_dir)):
        stem, ext = os.path.splitext(fname)
        if ext != ".json" or not stem.startswith("BENCH") \
                or stem == "BENCH_summary":
            continue
        path = os.path.join(bench_dir, fname)
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as err:
            summary[stem] = {"error": str(err)}
            continue
        entry = {"file": fname, "quick": bool(data.get("quick", False))}
        extractor = _HEADLINES.get(stem)
        if extractor is not None:
            try:
                entry["headline"] = extractor(data)
            except (KeyError, TypeError) as err:
                entry["headline_error"] = repr(err)
        # record any seed config the bench declares, so two summaries are
        # comparable only when they measured the same draw
        seeds = {k: v for k, v in data.items()
                 if isinstance(k, str) and "seed" in k.lower()}
        if seeds:
            entry["seeds"] = seeds
        summary[stem] = entry
    return summary


def run_all(bench_dir: str) -> None:
    summary = aggregate_all(bench_dir)
    print("bench,quick,headline")
    for stem, entry in summary.items():
        headline = entry.get("headline", entry.get("headline_error",
                                                   entry.get("error", "")))
        hl = "|".join(f"{k}={v}" for k, v in headline.items()) \
            if isinstance(headline, dict) else str(headline)
        print(f"{stem},{int(entry.get('quick', False))},{hl}")
    # the scenario catalog every bench builds its nodes from, as data
    from repro.api import describe_presets
    summary["_presets"] = describe_presets()
    print(f"# node presets: {','.join(sorted(summary['_presets']))}",
          file=sys.stderr)
    # deterministic artifact: stable key order, no timestamps — two runs
    # over identical BENCH_*.json inputs produce byte-identical output
    path = os.path.join(bench_dir, "BENCH_summary.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=1, default=str, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


def main() -> None:
    # invokable from anywhere: python benchmarks/run.py | python -m benchmarks.run
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    if "--all" in sys.argv[1:]:
        run_all(os.path.dirname(os.path.abspath(__file__)))
        return
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (bench_engine_speedup, bench_gas,
                            bench_l1_throughput, bench_l2_throughput,
                            bench_latency, bench_protocol, bench_prover,
                            bench_reputation, bench_roofline, bench_shards)

    quick = os.environ.get("BENCH_QUICK", "") not in ("", "0", "false")
    results = {}
    print("name,us_per_call,derived")

    out, us = _timed(bench_reputation.run)
    results["fig3_reputation_dynamics"] = {"us_per_call": us, "out": out}
    print(f"fig3_reputation_dynamics,{us:.0f},"
          f"good={out['good_final']:.3f}|malicious={out['malicious_final']:.3f}"
          f"|lazy={out['lazy_final']:.3f}")

    out, us = _timed(bench_l1_throughput.run)
    results["fig4_l1_throughput_latency"] = {"us_per_call": us, "out": out}
    print(f"fig4_l1_throughput_latency,{us:.0f},"
          f"peak_tps_submitLocalModel={out['peak_tps_submitLocalModel']:.0f}")

    out, us = _timed(bench_gas.run)
    n_rows = len(out["rows"])
    results["table1_gas_l1_vs_l2"] = {"us_per_call": us / max(n_rows, 1),
                                      "out": out}
    print(f"table1_gas_l1_vs_l2,{us / max(n_rows, 1):.0f},"
          f"max_gas_reduction={out['max_reduction']}x")

    out, us = _timed(bench_l2_throughput.run)
    results["fig5_l2_vs_l1_throughput"] = {"us_per_call": us, "out": out}
    print(f"fig5_l2_vs_l1_throughput,{us:.0f},"
          f"avg_l2_tps={out['avg_l2_tps']:.0f}|best_l2_tps={out['best_l2_tps']:.0f}")

    out, us = _timed(bench_latency.run)
    results["table2_l2_latency"] = {
        "us_per_call": us / max(len(out["rows"]), 1), "out": out}
    print(f"table2_l2_latency,{us / max(len(out['rows']), 1):.0f},"
          f"worst_rel_err={out['worst_rel_err_n>=10']}")

    out, us = _timed(bench_engine_speedup.run, quick=quick)
    results["engine_vector_speedup"] = {"us_per_call": us, "out": out}
    print(f"engine_vector_speedup,{us:.0f},"
          f"speedup={out['speedup']}x|n_txs={out['n_txs']}"
          f"|quick={int(out['quick'])}")

    if not quick:
        # quick/CI mode skips this one: the dedicated bench-shards-smoke
        # CI job already runs the reduced 2-shard config (running it here
        # too would duplicate the compute and the artifact)
        out, us = _timed(bench_shards.run, quick=False)
        results["shard_fabric_scaling"] = {"us_per_call": us, "out": out}
        print(f"shard_fabric_scaling,{us:.0f},"
              f"scaling={out['scaling']}x|shards={out['shard_counts'][-1]}"
              f"|state_root={out['state_root']}|quick=0")

    if not quick:
        # quick/CI mode skips this one: the dedicated bench-prover-smoke
        # CI job already runs the reduced width sweep (running it here too
        # would duplicate the compute and the artifact)
        out, us = _timed(bench_prover.run, quick=False)
        results["prover_aggregation_sweep"] = {"us_per_call": us, "out": out}
        print(f"prover_aggregation_sweep,{us:.0f},"
              f"verify_gas_reduction={out['reduction']}x"
              f"|widths={out['widths'][-1]}|quick=0")

    if not quick:
        # quick/CI mode skips this one: the dedicated bench-protocol-smoke
        # CI job already runs the reduced sweep (running it here too would
        # duplicate the compute and double the timing-assert flake surface)
        out, us = _timed(bench_protocol.run, quick=False)
        results["protocol_multitask_scheduler"] = {"us_per_call": us,
                                                   "out": out}
        sch_point = out["scheduler_grid"][
            "tasks={n_tasks},trainers={n_trainers}".format(
                **out["assert_point"])]
        print(f"protocol_multitask_scheduler,{us:.0f},"
              f"speedup={out['speedup']}x|tps={sch_point['tps']}"
              f"|gas_reduction={sch_point['gas_reduction']}x|quick=0")

    out, us = _timed(bench_roofline.run)
    s = out["summary"]
    results["roofline_dryrun_cells"] = {"us_per_call": us, "summary": s}
    print(f"roofline_dryrun_cells,{us:.0f},"
          f"ok={s['n_ok']}|err={s['n_error']}|skip={s['n_skipped']}"
          f"|dominant={s['dominant_histogram']}")

    path = os.environ.get(
        "BENCH_JSON",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH.json"))
    with open(path, "w") as f:
        json.dump({"quick": quick, "results": results}, f, indent=1,
                  default=str, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


if __name__ == '__main__':
    main()
