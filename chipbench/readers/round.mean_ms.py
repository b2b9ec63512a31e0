"""Window length over the rounds committed in it (median validator)."""


def read(obs):
    rounds = obs["window"]["rounds"]
    return 1000.0 * obs["window"]["seconds"] / rounds if rounds else None
