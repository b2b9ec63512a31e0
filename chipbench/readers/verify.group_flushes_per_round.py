"""Group-lane flushes (certificate proofs) per committed round; 0 means the lane never ran."""


def read(obs):
    f, rounds = obs["window"]["flushes"], obs["window"]["rounds"]
    return f.get("groups", 0) / rounds if rounds else None
