"""Mean of the seal stage over the window: histogram sum over count, all validators."""


def read(obs):
    total, count = obs["window"]["stages"]["seal"]
    return 1000.0 * total / count if count else None
