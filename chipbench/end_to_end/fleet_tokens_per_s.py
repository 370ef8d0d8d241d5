"""fleet_tokens_per_s: committed tokens of every replica group per second of
the fleet's window (later group's opening fetch to earlier group's closing
fetch). A metric of its own because the fleet is host-bound today: tens of
times lower than one chip alone, with noise of another kind, and a bound is
one number for every cell that reports a metric."""


def read(obs):
    return obs["tokens"] / obs["window_s"]
