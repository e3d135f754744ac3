"""Engine step: compile requests JAX saw inside the window (all of them
persistent-cache hits once warm) per mixed prefill step. The eager step
traces, lowers and asks the cache again for every layer; 0 is the aim."""


def read(obs):
    steps = obs.get("engine_steps")
    if not steps or not steps["mixed_steps"]:
        return None
    return obs["compile_requests"] / steps["mixed_steps"]
