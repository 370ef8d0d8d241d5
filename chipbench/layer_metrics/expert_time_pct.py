"""expert_time_pct: device seconds in the routed expert layer's kernels over
the window's busy device seconds. The kernels are the Pallas calls of the step
programs that the cell's architecture file names (``EXPERT_KERNEL``, a pattern
over their names in the device trace: the scope the program traces them under),
forward and both gradients. What the file names is the cell's to say, and the
two listed cells differ by one pair of calls (decided by PR 64, which listed the
second): the Keye file names the grouped product alone (``gmm``, ``tgmm``), as it
has since PR 46, so that cell's reading keeps its meaning across PR 51, which
made the weighted sum back to token order a Mosaic call; the windowed file
(PR 54, written after PR 51) names that sum and its transpose too
(``sum_by_token``, ``transpose_jvp_sum_by_token__``: 0.3 to 0.5 ms a layer step
beside the product's 8 to 14), because there the two are one kernel-bound
mechanism that a fused dispatch would replace together. So the windowed cell
reads the product AND the sum, the Keye cell the product; compare a reading
with the same cell's. The rest of the routing (the sort of the rows by expert,
the gathers) is XLA's and in neither. The layer is dropless, so these seconds
follow the rows that ARRIVED, which the routing decides and no reader sees:
there is no share of the peak beside this one (PERF.md section 7, PR 46).

Whom it is for: a cell whose architecture file has ``EXPERT_KERNEL``; where the
program has no such call (a parent without the kernels) nothing is read."""

from pathlib import Path

from chipbench.spec import load_module


def architecture_of(obs):
    here = Path(__file__).resolve().parents[1]
    return load_module(here / "architectures" / (obs["config"]["model_type"] + ".py"))


def expert_seconds(trace, architecture) -> float:
    named = getattr(architecture, "EXPERT_KERNEL", None)
    if named is None:
        return 0.0
    return sum(
        s for rows in trace["kernels"].values() for name, s in rows if named.search(name)
    )


def read(obs):
    trace = obs.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    seconds = expert_seconds(trace, architecture_of(obs))
    return 100.0 * seconds / trace["busy_s"] if seconds else None
