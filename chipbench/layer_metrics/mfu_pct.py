"""mfu_pct: model FLOP/s utilisation: tokens/s of the window times the
operations a trained token requires (chipbench/flops.py: 6 * N_matmul +
12 * L * d * s, no embedding gather, no recomputation) over chips times the
published bf16 peak (chipbench/peaks.json). An end-to-end utilisation, not a
kernel's roofline share."""


def read(obs):
    if not obs.get("peaks") or not obs.get("window_s"):
        return None
    achieved = obs["tokens"] / obs["window_s"] * obs["flops_per_token"]
    return 100.0 * achieved / (obs["chips"] * obs["peaks"]["bf16_tflops"] * 1e12)
