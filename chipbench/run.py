#!/usr/bin/env python3
"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Runs one cell of BENCHMARK.json on the machine it is started on and prints, as
the last line of stdout, one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``. It exits non-zero, and
prints no result, when JAX finds no TPU or fewer chips than the cell asks for.
Progress goes to stderr.

    --trace 0  the measured window of ``--seconds``: the end-to-end metrics
    --trace 1  a traced run of its own (a short window under the profiler):
               the per-layer metrics and a ``breakdown``
    --trace 2  one process measures and then traces: exactly ``--trace 0``
               up to the taking of its numbers (``correct``, ``attempted``,
               ``failed`` and the end-to-end metrics are that window's), then
               one capture thrown away, then ``trace_seconds`` of the same
               traffic under the program's capture control. ``metrics`` holds
               both kinds side by side: a per-layer metric whose ``source`` is
               ``device_trace`` or ``program_span`` reads the traced tail (as
               do ``breakdown`` and the device's ``busy_s`` / ``window_s``),
               one whose ``source`` is ``program_counter`` or ``host_clock``
               reads the measured window, so that ``host_stall_ms`` is the
               pause of the run that gave the rate

    --out DIR        also write the per-step series (and a sample of the
                     trace) there
    --rehearse FILE  a rehearsal, asked for by name: sizes overridden from
                     FILE, whatever platform answers; its line says
                     "rehearsal": true and is never a chip result
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from chipbench import harness, spec  # noqa: E402


# A per-layer metric from one of these sources needs the profiler or the
# capture's journal, so under ``--trace 2`` it reads the traced tail; any other
# (``program_counter``, ``host_clock``) reads the window that gave the rate.
TRACED_SOURCES = ("device_trace", "program_span")


def metric_values(bench, workload: str, group: str, obs, tail=None) -> dict:
    """{name: {"value", "unit"}} of the cell's metrics; a reader that finds
    nothing to read returns None and its metric is left out of the line.
    ``tail``: the traced tail's observations of a ``--trace 2`` run, which the
    metrics of ``TRACED_SOURCES`` read in place of ``obs``."""
    out = {}
    for metric in bench.metrics_of(workload, group):
        traced = tail is not None and metric["source"] in TRACED_SOURCES
        value = bench.reader(group, metric["name"]).read(tail if traced else obs)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    started = harness.process_start()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument(
        "--trace", type=int, choices=(0, 1, 2), default=0,
        help="0: the measured window, end-to-end metrics; 1: a traced window of its "
        "own, per-layer metrics; 2: the measured window, then a traced tail in the "
        "same process, both kinds of metric on one line",
    )
    parser.add_argument("--out", type=Path)
    parser.add_argument("--rehearse", type=Path)
    parser.add_argument("--worker", type=Path, help=argparse.SUPPRESS)
    parser.add_argument("--started", type=float, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)

    bench = spec.Benchmark(ROOT)
    faults = spec.problems(bench)
    if faults:
        raise SystemExit("no result: BENCHMARK.json is unsound:\n  " + "\n  ".join(faults))
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    if args.rehearse:
        overlay = json.loads(args.rehearse.read_text())
        config = {**config, **overlay.get("config", {})}
        config["run"] = {**config["run"], **overlay.get("run", {})}
        traffic = {**traffic, **overlay.get("traffic", {}).get(cell["traffic"], {})}
        from chipbench import reference

        # A toy sequence must still be several of the reference's blocks.
        for constant, value in overlay.get("reference", {}).items():
            setattr(reference, constant, value)
    run = harness.Run(
        cell, config, bench.architecture(config["model_type"]), traffic,
        args.seed, args.seconds, args.trace,
        args.started if args.started is not None else started,
        args.rehearse, args.out,
    )
    job = bench.job(traffic["job"])
    if args.worker:
        job.worker(run, args.worker)
        return 0
    outcome = job.run(run)
    for problem in outcome["problems"]:
        harness.say(f"NOT CORRECT: {problem}")
    obs, tail = outcome["obs"], outcome.get("tail")
    device = dict(outcome["device"])
    breakdown = None
    if args.trace:
        trace = (tail or obs).get("trace")  # the tail's under --trace 2
        if trace is None and not args.rehearse:
            raise SystemExit("no result: the traced run holds no device operation")
        if trace is not None:
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            breakdown = {
                "device_ops": trace["ops"][:10], "idle_gaps": trace["gaps"][:10],
            }
    metrics = {}
    if args.trace != 1:
        metrics.update(metric_values(bench, cell["name"], "end_to_end", obs))
    if args.trace:
        metrics.update(metric_values(bench, cell["name"], "per_layer", obs, tail))
    print(spec.result_line(
        outcome["correct"], outcome["attempted"], outcome["failed"], metrics,
        device, breakdown, rehearsal=bool(args.rehearse),
    ), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    # Leave at once: every process and server the job started is stopped by
    # now, and an interpreter's teardown races the native plane's threads that
    # `shutdown(wait=False)` left to die (seen under load on the CPU: "FATAL:
    # exception not rethrown", exit -6, after the result was printed).
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
