"""tokens_per_s: tokens trained per second on the one chip, over all the work
and all the time of the window: the tokens of the whole units done, divided
by the time between the opening and the closing fetch."""


def read(obs):
    return obs["tokens"] / obs["window_s"]
