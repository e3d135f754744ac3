"""Engine step: median, over all requests of the traced batches, of first
token minus submit. Today one eager mixed step of host work (PERF.md
section 2 says why it is not an end-to-end metric of this first benchmark)."""


def read(obs):
    return obs.get("first_token_ms")
