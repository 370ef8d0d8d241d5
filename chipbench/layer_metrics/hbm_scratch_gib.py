"""hbm_scratch_gib: the loaded programs' scratch on the fullest chip: the
largest memory_stats()["bytes_reserved"] sampled through the window (XLA's
temporaries, reserved at the bottom of memory while a program is loaded).
Sampled, because the runtime's own peak_bytes_reserved is a peak of the
process's whole life and set-up's float32 reference is the largest program."""


def read(obs):
    value = obs.get("scratch_peak_bytes")
    return None if value is None else value / 2**30
