"""jax's persistent-cache monitoring events during set-up and the run:
hits over requests, in percent. None where nothing asked the cache."""


def read(obs, args):
    ev = obs["ctx"].cache_events
    return 100.0 * ev["hits"] / ev["requests"] if ev["requests"] else None
