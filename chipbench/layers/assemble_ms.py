"""Host time of result assembly per request (ms): the span of
``jax_device_loop._assemble``."""


def read(obs):
    rows = [s.get("_assemble", 0.0) for s in obs.spans if s]
    return 1e3 * sum(rows) / len(rows) if rows else None
