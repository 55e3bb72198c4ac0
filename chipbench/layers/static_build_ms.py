"""Host time of the static build per request (ms): the spans of
``jax_device_loop.build_static`` and ``draw_jitter``."""


def read(obs):
    rows = [s.get("build_static", 0.0) + s.get("draw_jitter", 0.0)
            for s in obs.spans if s]
    return 1e3 * sum(rows) / len(rows) if rows else None
