"""setup_s: seconds from the start of the process to the window: JAX's
start, the engine's build, the load of the resident keys, the warm
ticks and, in a run that compiles, compilation."""


def read(obs):
    return obs.setup_s
