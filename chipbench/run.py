#!/usr/bin/env python3
"""The benchmark's one command.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and prints, as
the last line of stdout, one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (``--trace 1``: the
cell's per-layer metrics and a ``breakdown``; ``--trace 0``: its end-to-end
metrics). It exits non-zero, and prints no result, when JAX finds no TPU or
fewer chips than the cell asks for. Progress goes to stderr.

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


def metric_values(bench, workload: str, group: str, obs) -> dict:
    """{name: {"value", "unit"}} of the cell's metrics; a reader that finds
    nothing to read returns None and its metric is left out of the line."""
    out = {}
    for metric in bench.metrics_of(workload, group):
        value = bench.reader(group, metric["name"]).read(obs)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    started = harness.process_start()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
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
        args.seed, args.seconds, bool(args.trace),
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
    obs = outcome["obs"]
    device = dict(outcome["device"])
    breakdown = None
    if args.trace:
        trace = obs.get("trace")
        if trace is None and not args.rehearse:
            raise SystemExit("no result: the traced run holds no device operation")
        if trace is not None:
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            breakdown = {
                "device_ops": trace["ops"][:10], "idle_gaps": trace["gaps"][:10],
            }
    metrics = metric_values(
        bench, cell["name"], "per_layer" if args.trace else "end_to_end", obs
    )
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
