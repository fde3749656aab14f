"""One cell through ``run.py`` with the program's span recorder on, and the
span, counter and scope metrics read.

    python3 benchmarks/span_run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1> [--recorder off] [--spans-out FILE]

``run.py`` switches nothing on inside the program, hands its readers no
span record and registers no reader from outside ``lib/readers.py``; a PR
that is not a benchmark PR may not edit it.  This file is ``run.py`` with
those few lines laid over it from outside, for the metrics whose data files
wait in ``metrics/`` without a ``BENCHMARK.json`` entry (PERF.md section 7
names the lines; once they are in ``run.py`` this file goes).  Everything
that computes ``round_s``, ``setup_s``, the window, the check and the
accepted metrics is ``run.py``'s own code, untouched:

  * the program's recorder is switched on through its documented switch,
    ``TelemetryConfig.export_trace`` (``--recorder off`` leaves the
    operator's default, for the on-against-off cost);
  * under ``--trace 1`` the span record (``trace.json``), the profiler's
    host plane and the operations' ``op_name`` are read after the trace is
    stopped, every idle instant of the traced round is given to the deepest
    span open on the round's thread (``breakdown.idle_gaps`` then shows
    that attribution), and the waiting metric files are read like any
    other;
  * what has no place in ``run.py``'s result line (``host_self``, the two
    clocks' disagreement, the scopes, the tree's checks) goes to stderr as
    one ``[spans] {json}`` line and to ``--spans-out``.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import statistics
import sys

import run as bench
from lib import spans as spans_lib
from lib import trace as trace_lib

SCOPES = ("pool_gather", "view", "forward", "score_head",
          "forward_backward", "optimizer")
PHASES = ("query_time", "init_network_weights_time", "train_time",
          "load_best_ckpt_time", "test_time")


def waiting_metrics() -> list:
    """The metric files that no ``BENCHMARK.json`` entry names yet."""
    manifest = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    listed = {m["name"] for m in manifest["per_layer"]}
    found = []
    for path in sorted(glob.glob(os.path.join(
            bench.ROOT, manifest["paths"][0], "metrics", "*.json"))):
        spec = bench.load_json(path)
        if spec["name"] not in listed:
            found.append(spec)
    return found


def find_record(trace_dir: str):
    """The program's ``trace.json``, under the run's log directory."""
    logs = os.path.join(os.path.dirname(trace_dir), "logs")
    files = sorted(glob.glob(os.path.join(logs, "**", "trace*.json"),
                             recursive=True))
    return files[-1] if files else None


def read_spans(ctl, red: dict, planes: list, extras_out: dict) -> None:
    """Lay the span record over the traced round's reduction (in place)."""
    path = find_record(ctl.trace_dir)
    if path is None:
        bench.log("no span record: the program's recorder was off")
        return
    record = spans_lib.load_record(path)
    spans = record["spans"]
    anchor = trace_lib.first_event_ns(planes, "bench_anchor")
    by_anchor = anchor - int(ctl.trace_anchor * 1e9)    # perf_counter -> trace
    extras = spans_lib.load_trace_extras(
        trace_lib.find_xplane(ctl.trace_dir), {s["name"] for s in spans})
    pairs = spans_lib.paired(spans, extras["host"],
                             lambda t: int(t * 1e9) + by_anchor)
    clock = spans_lib.clock_disagreement(pairs)
    # Where the host plane has the annotations, tie the record to the
    # trace's clock by them (the median shift of the paired span starts),
    # not by the runner's anchor program.
    offset = by_anchor + (int(statistics.median(
        c - a for a, _, c, _ in pairs)) if pairs else 0)

    def to_ns(t: float) -> int:
        return int(t * 1e9) + offset

    t0 = int(ctl.trace_span[0] * 1e9) + by_anchor
    t1 = int(ctl.trace_span[1] * 1e9) + by_anchor
    on_trace = [{**s, "t0": to_ns(s["t0"]), "t1": to_ns(s["t1"])}
                for s in spans]
    rounds = [s for s in on_trace if s["name"] == "round"]
    if not rounds:
        return
    timeline = spans_lib.deepest_timeline(on_trace, rounds[0]["tid"])
    first = planes[0]["lines"]
    ops = first.get(trace_lib.OP_LINE) or first.get(trace_lib.MODULE_LINE, ())
    idle = {k: v / 1e9 for k, v in spans_lib.idle_by_span(
        spans_lib.device_gaps(ops, t0, t1), timeline).items()}
    red["spans_record"] = record
    red["idle_by_span"] = idle
    red["idle_gaps"] = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    if extras["hlo"]:
        named = spans_lib.name_ops(first.get(trace_lib.MODULE_LINE, ()),
                                   first.get(trace_lib.OP_LINE, ()),
                                   extras["hlo"])
        red["scope_s"] = spans_lib.scope_seconds_of(named, t0, t1, SCOPES)
        bench.log(f"op_name: {sum(1 for n in named if n[0])} of {len(named)} "
                  f"operations named from {len(extras['hlo'])} programs")
        # Which code the breakdown's largest operations belong to.
        made_by = {key: path for path, _, _, key in named}
        extras_out["device_ops_op_name"] = [
            [key, made_by.get(key, "")] for key, _ in red.get("top_ops", ())]

    # The traced round: the one whose ``round`` span shares most of the
    # window (its end and the trace's stop lie a dispatch apart).
    traced = max(rounds, key=lambda s: min(s["t1"], t1) - max(s["t0"], t0))
    selfs = spans_lib.self_seconds(spans)
    by_id = {s["id"]: s for s in spans}
    total_idle = sum(idle.values())
    shallow = sum(v for k, v in idle.items()
                  if k in PHASES or k in ("round", "experiment",
                                          spans_lib.UNATTRIBUTED))
    extras_out.update({
        "clock": clock,
        "host_annotations": len(extras["host"]),
        "scope_s": red.get("scope_s"),
        "idle_total_s": total_idle,
        "idle_deeper_than_phase_share": (
            1.0 - shallow / total_idle if total_idle else None),
        "idle_unattributed_share": (
            idle.get(spans_lib.UNATTRIBUTED, 0.0) / total_idle
            if total_idle else None)})
    rd = traced["round"]
    mine = [s for s in spans if s["round"] == rd]
    subtree = [s for s in mine if s["id"] == traced["id"]
               or spans_lib.has_ancestor(s, by_id, "round")]
    per_name: dict = {}
    total: dict = {}
    for s in mine:
        per_name[s["name"]] = per_name.get(s["name"], 0.0) + selfs[s["id"]]
        total[s["name"]] = total.get(s["name"], 0.0) + s["t1"] - s["t0"]
    round_s = (traced["t1"] - traced["t0"]) / 1e9
    # [round, round_epilogue] seconds of every round, net of the runner's
    # own pauses (it stops the trace at a boundary, inside an epilogue): the
    # pair adds up to the runner's seconds for that round.
    per_round: dict = {}
    for s in spans:
        if s["name"] in ("round", "round_epilogue") and s["round"] is not None:
            held = sum(max(0.0, min(p1, s["t1"]) - max(p0, s["t0"]))
                       for p0, p1 in ctl.pauses)
            pair = per_round.setdefault(int(s["round"]), [0.0, 0.0])
            pair[s["name"] == "round_epilogue"] += s["t1"] - s["t0"] - held
    if "round_epilogue" in per_name:    # a leaf: its self time is its length
        per_name["round_epilogue"] = per_round[int(rd)][1]
    extras_out.update({
        "round_and_epilogue_s": per_round,
        "traced_round": rd,
        "round_span_s": round_s,
        "round_epilogue_s": per_round[int(rd)][1],
        "subtree_self_sum_s": sum(
            selfs[s["id"]] for s in subtree
            if s["tid"] == by_id[traced["id"]]["tid"]),
        "host_self": sorted(per_name.items(), key=lambda kv: -kv[1])[:10],
        "span_s": {k: v for k, v in total.items() if "/" in k
                   or k in ("collect_pool", "epoch")}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--recorder", choices=("on", "off"), default="on")
    ap.add_argument("--spans-out", default=None)
    own, rest = ap.parse_known_args(argv)
    extras_out: dict = {}

    orig_build, orig_load, orig_read = (
        bench.build_configs, bench.load_cell, bench.read_trace)

    def build_configs(*a, **kw):
        cfg, train_cfg = orig_build(*a, **kw)
        if own.recorder == "on":
            cfg = dataclasses.replace(cfg, telemetry=dataclasses.replace(
                cfg.telemetry, export_trace=True))
        return cfg, train_cfg

    def load_cell(args):
        cell = orig_load(args)
        cell["metrics"] = cell["metrics"] + waiting_metrics()
        return cell

    def read_trace(ctl, sink):
        red, planes = orig_read(ctl, sink)
        read_spans(ctl, red, planes, extras_out)
        return red, planes

    bench.build_configs, bench.load_cell, bench.read_trace = (
        build_configs, load_cell, read_trace)
    try:
        rc = bench.main(rest)
    finally:
        bench.build_configs, bench.load_cell, bench.read_trace = (
            orig_build, orig_load, orig_read)
    if extras_out:
        line = json.dumps(extras_out)
        print(f"[spans] {line}", file=sys.stderr, flush=True)
        if own.spans_out:
            os.makedirs(os.path.dirname(os.path.abspath(own.spans_out)),
                        exist_ok=True)
            with open(own.spans_out, "w") as fh:
                fh.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
