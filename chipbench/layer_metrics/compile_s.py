"""compile_s: seconds in backend compilations (cache retrievals included)
during set-up, from jax.monitoring; for several processes, the slowest."""


def read(obs):
    return obs["compile"]["seconds"]
