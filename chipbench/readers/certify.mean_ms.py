"""Mean of the certify stage over the window: histogram sum over count, all validators."""


def read(obs):
    total, count = obs["window"]["stages"]["certify"]
    return 1000.0 * total / count if count else None
