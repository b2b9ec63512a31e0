"""Device flushes (singles + groups) per committed round."""


def read(obs):
    f, rounds = obs["window"]["flushes"], obs["window"]["rounds"]
    return (f.get("singles", 0) + f.get("groups", 0)) / rounds if rounds else None
