"""hbm_held_gib: the most any chip held at ONE instant, as far as the harness
saw: the largest memory_stats()["bytes_in_use"] + ["bytes_reserved"] (live
arrays plus the loaded programs' scratch) of the samples a thread takes every
5 ms through the window (harness.MemoryGauge). Never a sum of two peaks and
never clipped; it can read under the true peak (a transient between samples),
never over it."""


def read(obs):
    value = obs.get("held_peak_bytes")
    return None if value is None else value / 2**30
