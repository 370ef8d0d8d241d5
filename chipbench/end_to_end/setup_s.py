"""setup_s: from the start of the benchmark's process to the opening fetch of
the window: imports, the native plane's build, weights, the reference,
compilation (or its cache), warm-up. A restarted group pays it after every
failure."""


def read(obs):
    return obs["setup_s"]
