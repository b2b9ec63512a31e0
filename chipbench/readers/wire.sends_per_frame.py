"""Socket sends a frame cost in the window (`WireStats.sends` over
`WireStats.frames_sent`): the transport writes that found the write buffer
empty, where asyncio's `write` calls the socket's `send` at once. Two where a
frame's header and body each reach an empty buffer. None on a program that
does not count them."""


def read(obs):
    wire = obs["window"]["wire"]
    return wire["sends"] / wire["frames_sent"] if "sends" in wire and wire["frames_sent"] else None
