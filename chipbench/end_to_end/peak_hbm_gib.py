"""peak_hbm_gib: whether the state still fits: the largest
memory_stats()["peak_bytes_in_use"] over the cell's devices and processes,
read after the window, as ISSUE 24 defined it. Exact and the same in every run.
On this runtime it is live arrays (parameters, optimizer state, the FT paths'
extra copies, payloads in flight) WITHOUT the loaded programs' scratch, which
the per-layer metrics hbm_scratch_gib and hbm_held_gib show
(harness.MemoryGauge says why the two are never added)."""


def read(obs):
    return obs["arrays_peak_bytes"] / 2**30
