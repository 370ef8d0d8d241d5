"""host_stall_ms: the longest oversleep of a harness thread that sleeps 5 ms
at a time through the window (harness.HostPulse). It oversleeps when the whole
host stands still (a paused VM, a starved core), not when only the step loop
waits; a run whose rate reads far off with a large host_stall_ms met the
machine, not the program (my chip runs, PR 24: stalls of 0.1 to 10 s a few
minutes into a machine's life)."""


def read(obs):
    value = obs.get("host_stall_s")
    return None if value is None else 1e3 * value
