"""KV cache: pool blocks in use at their peak over blocks held."""


def read(obs):
    pool = obs.get("pool")
    if not pool:
        return None
    return 100.0 * pool["peak_blocks_in_use"] / pool["num_blocks"]
